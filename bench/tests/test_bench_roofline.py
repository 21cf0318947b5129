"""The counts behind step_mfu_pct and kv_path_roofline, by hand at
granite-3-8b's shapes (16 of 40 layers)."""
import pytest

from harness import roofline, spec


@pytest.fixture(scope="module")
def granite():
    cfg = spec.load_json(spec.BENCH / "configs" / "granite-3-8b.json")
    return dict(cfg, glu=True)


def test_weights(granite):
    # attention: wq, wo 4096 x 4096; wk, wv 4096 x 1024
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert attn == 41_943_040
    mlp = 3 * 4096 * 12800                      # SwiGLU: gate, up, down
    head = 49155 * 4096                         # tied: the embedding
    assert roofline.matmul_params(granite) == 16 * (attn + mlp) + head \
        == 3_389_009_920
    norms = (2 * 16 + 1) * 4096
    assert roofline.weight_bytes(granite) == 2 * (3_389_009_920 + norms) \
        == 6_778_290_176


def test_kv_counts(granite):
    # 16 layers x (k, v) x 8 kv heads x 128 x 2 bytes
    assert roofline.kv_token_bytes(granite) == 65_536
    # 16 layers x (scores + values) x 2 flops x 32 heads x 128 x 100 tokens
    assert roofline.attention_flops(granite, 100) == 26_214_400
    flops, nbytes = roofline.kv_work(granite, [100] * 8)
    assert flops == 8 * 26_214_400
    assert nbytes == (800 + 8) * 65_536


def test_step_least_time_is_byte_bound(granite):
    flops, nbytes = roofline.step_work(granite, [100] * 8)
    assert flops == 2 * 3_389_009_920 * 8 + 8 * 26_214_400 \
        == 54_433_873_920
    assert nbytes == 6_778_290_176 + 8 * 4096 * 2 + 808 * 65_536 \
        == 6_831_308_800
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_seconds(flops, nbytes, peak)
    assert bound == "bytes"
    assert t == pytest.approx(6_831_308_800 / 819e9)
    assert t == pytest.approx(8.341e-3, rel=1e-3)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v4")
    assert roofline.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_counts_do_not_depend_on_rounds_or_pages(granite):
    # the same visible tokens, split any way over sequences, read the same
    # KV bytes: nothing here knows pages, budgets or grid steps
    a = roofline.kv_work(granite, [1000, 24])
    b = roofline.kv_work(granite, [512, 512])
    assert a == b
