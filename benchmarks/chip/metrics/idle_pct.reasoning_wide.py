"""``idle_pct.reasoning`` in the deepseek-v2-lite-e16 cell: the share of
the traced window in which no operation ran on the device, in %.  A name
of its own keeps the dense cell's readings apart from this one."""
import registry

read = registry.Bench().reader("idle_pct.reasoning")
