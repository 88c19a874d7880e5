"""``batch_occupancy_pct.reasoning`` in the deepseek-v2-lite-e16 cell:
slots decoding per decode tick over max_batch in the window, in %, which
shows that the batch the cell is judged at is full.  A name of its own
keeps the dense cell's readings apart from this one."""
import registry

read = registry.Bench().reader("batch_occupancy_pct.reasoning")
