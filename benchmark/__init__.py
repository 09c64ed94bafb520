"""The ceph-tpu benchmark: one cell of BENCHMARK.json per run.

Everything here is the yardstick; the system under test is `ceph_tpu`.
"""
