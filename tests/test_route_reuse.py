"""Route programs stay on the host until their content changes.

* ``steering.make_program`` returns the program already on the device for
  a content it keeps, and puts a new one for a new content;
* ``ControlPlane.route_program`` verifies a content once per topology:
  an unchanged placement reuses the device program and skips the check,
  a change (last lease released, a failed link, another topology)
  verifies and installs again;
* an unchecked install is never remembered as verified;
* over an orchestrated run with churning leases the journal records the
  same digests as compiling and verifying every program from scratch, and
  replays.
"""
import numpy as np
import pytest

import repro.analysis.program_check as program_check
from repro.analysis.findings import ProgramVerificationError
from repro.core import steering
from repro.core.control_plane import ControlPlane
from repro.core.topology import Topology
from repro.obs import FlightRecorder, replay
from repro.obs.clock import ManualClock
from repro.obs.flight import program_digest
from repro.orchestrator import Orchestrator, TenantSpec


@pytest.fixture
def fresh_cache(monkeypatch):
    """An empty device-program cache, so installs count from zero."""
    monkeypatch.setattr(steering, "_INSTALLED", steering._DevicePrograms(
        steering.PROGRAM_CACHE_SIZE))


@pytest.fixture
def checks(monkeypatch):
    """Count the static verifier's runs."""
    calls = []
    real = program_check.check_program

    def counted(program, topology=None, **kw):
        calls.append(program_digest(program))
        return real(program, topology, **kw)

    monkeypatch.setattr(program_check, "check_program", counted)
    return calls


def _plane(topology=None, pages=8):
    cp = ControlPlane(4, 16, num_logical=64, seed=3, topology=topology)
    region = cp.allocate(pages, policy="striped")
    return cp, region


@pytest.mark.parametrize("topology", [None, Topology.boards(2, 2)],
                         ids=["flat", "boards"])
def test_unchanged_placement_reuses_the_device_program(fresh_cache, checks,
                                                       topology):
    cp, _ = _plane(topology)
    first = cp.route_program()
    assert steering.device_puts() == 1 and len(checks) == 1
    again = cp.route_program()
    assert again is first
    assert len(checks) == 1 and steering.device_puts() == 1
    c = cp.route_counts
    assert (c.compiled, c.installed, c.verified, c.verify_skipped) == (
        2, 1, 1, 1)
    # the builders themselves hand back the kept program
    kept = steering.bidirectional_program(4)
    assert steering.bidirectional_program(4) is kept


def _release_last(cp, region):
    cp.release(region)


def _fail_link(cp, region):
    cp.report_link_failure(+1)


def _boards(cp, region):
    cp.topology = Topology.boards(2, 2)


@pytest.mark.parametrize("change", [_release_last, _fail_link, _boards],
                         ids=["release_last_lease", "link_failure",
                              "other_topology"])
def test_a_change_of_content_verifies_and_installs_again(fresh_cache, checks,
                                                         change):
    cp, region = _plane()
    before = cp.route_program()
    assert cp.route_program() is before and len(checks) == 1
    change(cp, region)
    after = cp.route_program()
    assert program_digest(after) != program_digest(before)
    assert after is not before
    assert len(checks) == 2 and checks[-1] == program_digest(after)
    assert cp.route_counts.installed == 2
    assert cp.route_program() is after and len(checks) == 2


def test_same_content_on_another_topology_is_verified_again(fresh_cache,
                                                            checks):
    cp, _ = _plane()
    prog = cp.route_program()
    cp.topology = Topology.flat(4)          # equal fabric, new object
    assert cp.route_program() is prog
    assert checks == [program_digest(prog)] * 2
    other, _ = _plane()                     # another plane remembers nothing
    assert other.route_program() is prog
    assert len(checks) == 3


def _incongruent():
    h = steering.bidirectional_program(4).on_host()
    off = np.array(h.offsets)
    off[0] = 2                               # slot 0 must drive offset 1
    return steering.make_program(off, h.epoch, h.live, h.rank_epoch)


@pytest.mark.parametrize("sound", [True, False], ids=["sound", "corrupt"])
def test_unchecked_install_is_never_remembered_as_verified(fresh_cache,
                                                           checks, sound):
    cp, _ = _plane()
    prog = (steering.bidirectional_program(4) if sound
            else _incongruent())
    assert cp.route_program(program=prog, verify=False) is prog
    assert checks == [] and cp.route_counts.verified == 0
    if sound:
        assert cp.route_program(program=prog) is prog
        assert len(checks) == 1
    else:
        for _ in range(2):
            with pytest.raises(ProgramVerificationError):
                cp.route_program(program=prog)
        assert len(checks) == 2 and cp.route_counts.verify_skipped == 0


def _churn_run(topology):
    """40 orchestrator steps on the placement branch with leases granted,
    released and re-homed, and a ring link failing half way."""
    fr = FlightRecorder(clock=ManualClock())
    cp = ControlPlane(4, 16, num_logical=64, seed=5, topology=topology)
    orc = Orchestrator(cp, budget=8, control_period=2, migrate=False,
                       flight=fr)
    orc.register(TenantSpec(1, "chat", qos="interactive", share=3.0))
    orc.register(TenantSpec(2, "crawl", qos="batch", share=1.0))
    rng = np.random.default_rng(9)
    leases = []
    for step in range(40):
        if leases and rng.random() < 0.4:
            orc.release_lease(leases.pop(int(rng.integers(len(leases)))))
        if rng.random() < 0.6:
            _, lease = orc.request_lease(
                1 + step % 2, int(rng.integers(1, 4)),
                policy=str(rng.choice(["affinity", "striped"])),
                queue=False, request_id=step)
            if lease is not None:
                leases.append(lease)
        if step == 25:
            cp.report_link_failure(-1)
        orc.route_program()
        orc.step()
    return cp, fr


@pytest.mark.parametrize("topology", [None, Topology.boards(2, 2)],
                         ids=["flat", "boards"])
def test_journal_digests_match_compiling_from_scratch(monkeypatch,
                                                      topology):
    cp, fr = _churn_run(topology)
    kept = [r.detail["digest"] for r in fr.records("route_program")]
    c = cp.route_counts
    assert len(kept) == c.compiled == c.verified + c.verify_skipped
    assert c.verify_skipped > c.verified
    assert c.verified == len(set(kept))

    # Nothing kept: every program put on the device and verified anew.
    monkeypatch.setattr(steering, "PROGRAM_CACHE_SIZE", 0)
    monkeypatch.setattr(steering, "_INSTALLED", steering._DevicePrograms(0))
    scratch_cp, scratch = _churn_run(topology)
    fresh = [r.detail["digest"] for r in scratch.records("route_program")]
    assert fresh == kept
    assert scratch_cp.route_counts.verify_skipped == 0
    assert scratch_cp.route_counts.installed == len(fresh)

    res = replay(FlightRecorder.from_jsonl(fr.to_jsonl()))
    assert res.programs == len(kept)
