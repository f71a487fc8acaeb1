"""How many flash kernel bodies the process has traced
(`ops/pallas_attention.py:kernel_bodies`, counted where an emitter runs):
what the tracing and lowering of a train step scales with."""

try:
    from dalle_pytorch_tpu.ops import pallas_attention
except ImportError:  # a program without the kernels
    pallas_attention = None


def read(params: dict, ctx: dict):
    return getattr(pallas_attention, "kernel_bodies", None)
