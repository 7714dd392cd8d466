"""The benchmark: harness, data files, reduction and references.

``python -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` measures one cell of ``BENCHMARK.json`` on the TPU it is
started on.  Nothing here is imported by ``horovod_tpu``.
"""
