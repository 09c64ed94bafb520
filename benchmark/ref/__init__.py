"""Plain references the benchmark's `correct` is decided against.

They import nothing of `ceph_tpu`: each is written from the upstream
algorithm's definition (ISA-L's gf_gen_rs_matrix, crush/mapper.c)."""
