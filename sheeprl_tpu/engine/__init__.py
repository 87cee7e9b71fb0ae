"""Execution engines: reusable loop drivers that decide *when* things run
(concurrency, overlap, cadence), while the algorithms keep deciding *what*
runs (losses, agents, buffers)."""

from .overlap import OverlapEngine, Packet, RecordingSink, SpscRing, telem_span

__all__ = ["OverlapEngine", "Packet", "RecordingSink", "SpscRing", "telem_span"]
