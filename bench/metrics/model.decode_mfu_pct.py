"""Useful FLOPs of the decode tokens produced in the traced window (from
shapes, ``work.py``) over the device time of the decode step program in
that window times the chip's peak."""


def read(run):
    w = run.decode_work()
    if w is None:
        return None
    flops, _, seconds = w
    return 100.0 * flops / (seconds * run.peak["bf16_flops_per_s"])
