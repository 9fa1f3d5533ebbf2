"""InterpreterBackend: the FEATHER+ functional machine behind the Backend
interface.

This is the orchestration loop that used to live in
``core/machine.FeatherMachine.run``: walk a Program's TraceOp stream,
``step`` each instruction through the machine, ``flush`` the batched
Execute invocations at the end.  The machine itself (``core/machine.py``)
now only implements instruction semantics and architecture state.

The backend keeps one machine across ``run_program`` calls, so chained
Programs (paper §IV-G on-chip commit + input elision) execute exactly as
before: layer i's committing Write places data in the operand buffer and
layer i+1's elided input reads it from there.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Iterable

import numpy as np

from repro.backends.base import Backend
from repro.core.machine import FeatherMachine
from repro.obs.trace import trace

if TYPE_CHECKING:  # pragma: no cover
    from repro.configs.feather import FeatherConfig
    from repro.core.program import Program, TraceOp


class InterpreterBackend(Backend):
    """Tile-by-tile interpretation of the MINISA instruction stream."""

    name = "interpreter"

    def __init__(self, cfg: "FeatherConfig", max_depth: int | None = None):
        super().__init__(cfg)
        self.max_depth = max_depth
        self.machine = FeatherMachine(cfg, max_depth=max_depth)

    def _make_shard_backend(self, array: int) -> "InterpreterBackend":
        # one functional machine per logical array
        return InterpreterBackend(self.cfg, max_depth=self.max_depth)

    def run_trace(self, ops: Iterable["TraceOp"],
                  tensors: dict[str, np.ndarray] | None = None
                  ) -> dict[str, np.ndarray]:
        """Drive the machine over a flat TraceOp stream."""
        m = self.machine
        with trace.span("interpret.trace"):
            for op in ops:
                m.step(op, tensors)
            m.flush()
        self.outputs = m.outputs
        return m.outputs

    def run_program(self, program: "Program",
                    tensors: dict[str, np.ndarray] | None = None
                    ) -> dict[str, np.ndarray]:
        from repro.core.program import ShardedProgram
        if isinstance(program, ShardedProgram):
            return self.run_sharded(program, tensors)
        return self.run_trace(program.trace_ops(), tensors)

    def reset(self) -> None:
        super().reset()
        self.machine.reset()
