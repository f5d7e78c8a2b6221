"""`step_mfu` in the survey cell, where it moves `shots_per_s`."""
from harness import files

read = files.metric("step_mfu").read
