"""The paged decode step's share of its roofline: the least time the
chip needs for the decode calls of the traced window (the larger of their
useful FLOPs over peak FLOP/s and their least bytes over HBM bandwidth)
over the device time they took."""


def read(run):
    w = run.decode_work()
    if w is None:
        return None
    flops, nbytes, seconds = w
    least = max(flops / run.peak["bf16_flops_per_s"],
                nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
