"""Useful FLOPs of the prompts whose jobs ended in the traced window
(from shapes, ``work.py``) over the device time of the prefill program in
that window times the chip's peak."""

import work


def read(run):
    prog = run.program("prefill")
    if prog is None:
        return None
    flops = sum(work.prefill_flops(run.cell.conf, j.prompt_len)
                for j in run.done if run.in_trace(j.end))
    if not flops:
        return None
    return 100.0 * flops / (prog["seconds"] * run.peak["bf16_flops_per_s"])
