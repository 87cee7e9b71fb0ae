"""A block-diffusion env step's share of its roofline: the bytes its S
forwards for all envs must read (every parameter each forward touches, and the
cache rows in use: the adapter's `decode_bytes`) over the chip's HBM
bandwidth, over the step's device time per call. The reading of
`player.decode_roofline`, whose cells are the one-token decode steps'."""
from perfbench.run import metric_reader


def read(ctx):
    return metric_reader("player.decode_roofline")(ctx)
