"""`device_idle_pct` in the survey cell, where it moves `shots_per_s`."""
from harness import files

read = files.metric("device_idle_pct").read
