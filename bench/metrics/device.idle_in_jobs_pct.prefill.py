"""``device.idle_in_jobs_pct``, in the cells whose tail is ``response_p95_ms``."""

from pathlib import Path

from metrics_io import load_reader

read = load_reader(Path(__file__).parent, "device.idle_in_jobs_pct")
