"""The worst job response (due release to last token) over the
response-time bound the admission analysis proved for its stream: above
1, the analysis did not bound what the served path did."""


def read(run):
    r = [(j.end - j.due) * 1e3 / run.bounds[j.stream] for j in run.done
         if run.bounds.get(j.stream)]
    return max(r) if r else None
