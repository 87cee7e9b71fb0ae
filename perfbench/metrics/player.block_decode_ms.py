"""Device time of a block-diffusion policy's env step per call: the adapter's
`decode_programs`, S denoising forwards through the K/V cache in one program,
over the traced window. The reading of `player.decode_device_ms`, whose cells
are the one-token decode steps'."""
from perfbench.run import metric_reader


def read(ctx):
    return metric_reader("player.decode_device_ms")(ctx)
