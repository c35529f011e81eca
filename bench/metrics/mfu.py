"""Useful FLOPs of the requests completed in the traced window (``flops.py``,
two per MAC of the reference) over the window and over chips x the bf16
peak of ``peaks.json``, %.  The executor computes in float32 at HIGHEST;
the peak is the bf16 one a deployment would use."""


def read(record):
    tr = record.get("trace")
    if not tr or not tr["window_s"] or not record.get("traced_flops"):
        return None
    rate = record["traced_flops"] / tr["window_s"]
    return 100.0 * rate / record["peak_flops"]
