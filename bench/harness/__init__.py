"""The benchmark's yardstick: everything a run needs apart from the system
under test.

``spec`` finds a cell's configuration, traffic mix, check limits and metric
readers by name; ``weights`` makes the served weights from the seed;
``traffic`` turns a mix file into timed requests; ``serve`` drives the
served path for a measured window; ``check`` compares what it served with
the plain reference; ``trace`` and ``roofline`` reduce a profiler trace and
the cell's shapes to per-layer metrics.
"""
