"""``admission.resp_over_bound_max``, in the cells whose tail is ``response_p95_ms``."""

from pathlib import Path

from metrics_io import load_reader

read = load_reader(Path(__file__).parent, "admission.resp_over_bound_max")
