"""``mfu.decode`` in the deepseek-v2-lite-e16 cell: the decode steps' share
of the chip's roofline over the traced window, in %, through the model
code's ``decode_step`` (latent rows, the held experts a step touches).
A name of its own keeps the dense cells' readings apart from this one."""
import registry

read = registry.Bench().reader("mfu.decode")
