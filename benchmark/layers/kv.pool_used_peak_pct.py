"""Peak share of the KV pool's blocks in use: the engine's ``free_blocks``
sampled once a second by the benchmark, against the pool's size."""


def read(run):
    if "free_blocks_min" not in run:
        return None
    return 100.0 * (run["kv_blocks"] - run["free_blocks_min"]) / run["kv_blocks"]
