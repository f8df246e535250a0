"""Whether the timed path served the right tokens.

After the window, a sample of the finished jobs, drawn from the seed and
always holding the longest one, is run through the float32 reference,
one forward pass per job over its prompt and the tokens it was served.
The number compared is the widest gap by which a served token's
reference logit lies below the reference's best logit at that position:
greedy decoding in bfloat16 may pick a near-tie, never a token far below
the best.  Jobs that never answered, or answered with the wrong number of
tokens or with an id outside the vocabulary, make the run not correct.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

REFUSED = "stream refused by admission"


@dataclass
class Checks:
    numbers: dict = field(default_factory=dict)
    passed: dict = field(default_factory=dict)
    # the verdict with the control put in the program's place (only when
    # the control was run): the same rule, the control's gap compared
    control_correct: bool | None = None

    @property
    def correct(self) -> bool:
        return all(self.passed.values())

    def add(self, name: str, value, limit, ok: bool) -> None:
        self.numbers[name] = {"value": value, "limit": limit}
        self.passed[name] = ok

    def lines(self) -> list[str]:
        return [f"check {n}: {v['value']!r} (limit {v['limit']!r})"
                for n, v in self.numbers.items()]


def sample(jobs, seed: int, tokens: int) -> list:
    """Finished jobs: the longest first, then others in an order drawn
    from ``seed``, until they hold ``tokens`` served tokens."""
    done = [j for j in jobs if j.done]
    if not done:
        return []
    longest = max(done, key=lambda j: (j.prompt_len + j.steps, j.due))
    rest = [j for j in done if j is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [longest], longest.steps + 1
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += rest[i].steps + 1
    return out


def served(job) -> list[int]:
    return [job.first_token, *job.tokens]


def gaps(model, conf, weights, job, seq_len: int, n: int, *,
         pick=None) -> np.ndarray:
    """Per served position, the reference's best logit minus the logit of
    the token served there (or of ``pick(control_logits)`` when a control
    forward is given as ``pick``)."""
    toks = served(job)
    seq = np.zeros((seq_len,), np.int32)
    seq[: job.prompt_len] = np.asarray(job.prompt[0])
    seq[job.prompt_len: job.prompt_len + len(toks) - 1] = toks[:-1]
    start = job.prompt_len - 1
    ref = np.asarray(model.logits(conf, weights, seq, start, n),
                     np.float64)[: len(toks)]
    if pick is not None:
        toks = pick(seq, start, n)[: len(toks)]
    best = ref.max(axis=-1)
    return best - ref[np.arange(len(toks)), toks]


def control_pick(model, conf, weights):
    """The control's choice at each position: the first token of the
    reference computed with float8 matmul inputs."""
    def pick(seq, start, n):
        return np.asarray(model.logits(conf, weights, seq, start, n,
                                       fp8=True)).argmax(axis=-1)
    return pick


def served_tokens(cell, model, weights, jobs, seed: int, *,
                  control: bool = False) -> Checks:
    """The run's checks; with ``control`` also the control's reading on
    the same sample and the verdict it gets (``control_correct``), which
    has to come out false."""
    c = Checks()
    lost = [j for j in jobs
            if j.error is not None and j.error != REFUSED]
    c.add("jobs_without_answer", len(lost), 0, not lost)
    vocab = cell.conf["vocab_size"]
    bad = [j for j in jobs if j.done and (
        len(served(j)) != j.steps + 1
        or not all(0 <= t < vocab for t in served(j)))]
    c.add("jobs_malformed", len(bad), 0, not bad)
    picked = [j for j in sample(jobs, seed, cell.traffic["check_tokens"])
              if all(j is not b for b in bad)]
    eng = cell.traffic["engine"]
    n = max(g["steps"][1] for g in cell.traffic["groups"]) + 1
    widest = max((float(gaps(model, cell.conf, weights, j, eng["max_seq"],
                             n).max()) for j in picked), default=float("inf"))
    checked = sum(len(served(j)) for j in picked)
    c.add("tokens_checked", checked, cell.traffic["check_tokens"],
          checked >= min(cell.traffic["check_tokens"],
                         sum(len(served(j)) for j in jobs if j.done)) > 0)
    limit = cell.declared["limits"]["max_logit_gap"]
    c.add("max_logit_gap", widest, limit, within(widest, limit))
    if control:
        pick = control_pick(model, cell.conf, weights)
        ctl = max((float(gaps(model, cell.conf, weights, j, eng["max_seq"], n,
                              pick=pick).max()) for j in picked),
                  default=float("inf"))
        c.numbers["control_max_logit_gap"] = {"value": ctl, "limit": limit}
        c.control_correct = all(ok for k, ok in c.passed.items()
                                if k != "max_logit_gap") and within(ctl, limit)
    return c


def within(gap: float, limit: float) -> bool:
    """The rule that decides ``max_logit_gap``, for the program and for
    the control put in its place."""
    return gap <= limit
