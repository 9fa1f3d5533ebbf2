"""The GEMM-stream stand-in: the served path runs one instance of each
distinct layer's GEMMs (no norms, rotary, router or embeddings), and
``correct`` compares every launch of a seeded sample of the window's
prefill passes and decode ticks with ``reference.py`` beside this file.

``cell.py`` builds and warms the cell and checks the file against the
program, ``recorder.py`` keeps what the window produced,
``correctness.py`` compares it, ``accounting.py`` counts each launch's
work.
"""

from __future__ import annotations

from bench.models.gemm_stream import correctness
from bench.models.gemm_stream.accounting import launches
from bench.models.gemm_stream.cell import build, validate, warm_up
from bench.models.gemm_stream.correctness import NUMBERS, readings, required
from bench.models.gemm_stream.recorder import Recorder

__all__ = ["NUMBERS", "build", "launches", "readings", "recorder",
           "required", "validate", "warm_up"]

#: sampled calls of each kind whose answers are checked
SAMPLED_CALLS = {"prefill": 2, "decode": 8}


def recorder(sched, config: dict, mix: dict, seed: int) -> Recorder:
    """Sees every request from its admission on: create it before the
    first is submitted."""
    return Recorder(sched, seed, SAMPLED_CALLS,
                    correctness.served_lead(config, mix))
