"""Model FLOP/s utilization: the benchmark's own count of the operations a
token needs (``perfbench/flops.py``, recompute not counted) times the
tokens per second of the traced run, over chips times the published peak."""


def read(observed):
    if not observed.get("peak_flops"):
        return None
    return (100.0 * observed["flops_per_token"] * observed["tokens_per_s"]
            / (observed["chips"] * observed["peak_flops"]))
