"""`tb_kernel_roofline` in the survey cell, where it moves `shots_per_s`."""
from harness import files

read = files.metric("tb_kernel_roofline").read
