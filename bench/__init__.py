"""Chip benchmark of the serving path: one command, cells named in BENCHMARK.json.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, kernel or
metric is a file of its own, found by the name that BENCHMARK.json or
the traffic file gives it:

    configs/<config>.json      model sizes as run, source, cuts, departures
    reference/<name>.py        plain float32 reference: weights from the seed
                               and the forward pass (no program code)
    traffic/<traffic>.json     parameters of a mix; names its generator
                               (traffic/<generator>.py) and runner
                               (runners/<runner>.py)
    limits/<workload>.json     the limit of each number `correct` compares
    work/<kernel_id>.py        required FLOPs and bytes of one kernel call
    work/model_lm.py           required FLOPs and bytes of a decoder LM step
    metrics/<metric>.py        reads one metric from a run's record
    peaks.json                 chip peaks keyed by device_kind
"""
