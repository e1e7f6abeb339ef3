"""Share of the SSD kernel's roofline in a generation cell: the same
reading as ``ssd_scan_roofline`` (the kernel runs once per layer of every
prefill, never in decode), reported against the cell's generated tokens."""

from chipbench.spec import load_module

read = load_module("metrics", "ssd_scan_roofline").read
