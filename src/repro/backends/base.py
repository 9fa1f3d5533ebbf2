"""Execution-backend interface: how a lowered Program becomes numbers.

A :class:`Backend` consumes the tiled Program IR (``core/program.py``) and
produces the named output tensors.  Two implementations ship:

  interpreter  ``backends.interpreter.InterpreterBackend`` -- drives the
               FEATHER+ functional machine tile by tile (the semantics of
               every MINISA instruction, formerly the orchestration loop
               inside ``core/machine.py``)
  pallas       ``backends.pallas_backend.PallasBackend`` -- compiles the
               Program's tiling to one ``pl.pallas_call`` per layer
               (interpret-mode on CPU, Mosaic on TPU)

Backends are stateful across ``run_program`` calls within one instance:
chained Programs (paper §IV-G) resolve their elided/retargeted inputs
against the backend's committed outputs, exactly like the machine's
on-chip commit.  ``reset()`` clears that state.

Multi-array execution: ``run_program`` also accepts a
:class:`~repro.core.program.ShardedProgram` (dispatching to
:meth:`run_sharded`).  The base implementation keeps one sub-backend per
logical array -- each array is its own machine with its own buffers and
committed state -- runs every shard on its array's executor, and
assembles the host output (concatenation along the split rank, or an
explicit reduction for K-partitioned shards) before applying any hoisted
epilogue activation.  Subclasses may override ``run_sharded`` with a
genuinely parallel path (the Pallas backend shard_maps one kernel over a
JAX device mesh when available).
"""

from __future__ import annotations

import abc
import contextlib
from typing import TYPE_CHECKING

import numpy as np

from repro.core.program import ShardedProgram

if TYPE_CHECKING:  # pragma: no cover
    from repro.configs.feather import FeatherConfig
    from repro.core.program import Program


class Backend(abc.ABC):
    """Common interface over Program executors."""

    #: registry key; subclasses override
    name: str = "abstract"

    def __init__(self, cfg: "FeatherConfig"):
        self.cfg = cfg
        self.outputs: dict[str, np.ndarray] = {}
        #: kernel launches performed (only compiled backends bump this;
        #: the interpreter replays instructions, it does not launch)
        self.n_launches = 0
        #: bytes copied host->device and device->host (compiled backends
        #: only; the interpreter never leaves the host)
        self.n_h2d_bytes = 0
        self.n_d2h_bytes = 0
        #: weight operands found already resident on the device, so not
        #: copied (compiled backends only)
        self.n_weight_hits = 0
        #: what ``run_program``'s 'W' operand holds (see weight_kind)
        self._weight_kind = "weight"
        # one executor per logical array, created on first sharded run
        self._shard_subs: dict[int, "Backend"] = {}

    @abc.abstractmethod
    def run_program(self, program: "Program",
                    tensors: dict[str, np.ndarray] | None = None
                    ) -> dict[str, np.ndarray]:
        """Execute one lowered Program; returns all named outputs so far.

        Chained layer sequences (``program.chain``) are executed with one
        ``run_program`` call per layer on the same backend instance,
        passing each layer's own tensors (the default lowering names every
        layer's weight Load 'W', so a single shared dict would silently
        reuse layer 0's weights).

        A :class:`ShardedProgram` argument dispatches to
        :meth:`run_sharded`."""

    @contextlib.contextmanager
    def weight_kind(self, kind: str):
        """Within the block, ``run_program``'s 'W' operand holds ``kind``:
        ``kv`` for a dynamic step's K^T / V, ``weight`` otherwise.  The
        executable knows its tensors' kinds; a compiled backend labels
        its uploads with them."""
        prev, self._weight_kind = self._weight_kind, kind
        try:
            yield
        finally:
            self._weight_kind = prev

    # -- chained-segment execution -------------------------------------------
    def run_segment(self, segment, tensors: dict[str, np.ndarray] | None
                    = None) -> dict[str, np.ndarray]:
        """Execute a :class:`~repro.core.program.FusedSegment`.

        ``tensors`` carries the segment input as ``'I'`` and layer l's
        weight as ``'W{l}'``.  The base implementation replays the
        chained per-layer Programs on this backend (the chain semantics
        -- on-chip commit, elided/retargeted inputs -- come from the
        Programs themselves), applying the runtime ``adapt`` shape glue
        at the segment's interior adapt boundaries; subclasses with a
        genuinely fused path (the Pallas backend's one-launch
        megakernel, which lowers adapt to an in-kernel slab permutation)
        override it.  A :class:`~repro.core.program.ShardedFusedSegment`
        dispatches to the per-array path.
        """
        from repro.core.program import ShardedFusedSegment
        if isinstance(segment, ShardedFusedSegment):
            return self._run_sharded_segment(segment, tensors)
        from repro.runtime.executable import adapt
        tensors = tensors or {}
        adapts = getattr(segment, "adapts", None) \
            or (False,) * len(segment.programs)
        for layer, prog in enumerate(segment.programs):
            t = {"W": tensors[f"W{layer}"]}
            if layer == 0:
                if "I" in tensors:
                    t["I"] = tensors["I"]
            elif adapts[layer]:
                prev = self.outputs[segment.programs[layer - 1].out_name]
                g = prog.gemm
                t["I"] = adapt(np.asarray(prev), g.m, g.k)
            elif any(op.meta.get("operand") == "I"
                     and op.meta.get("tensor") == "I"
                     for tile in prog.tiles for op in tile.loads):
                # unchained sub-programs (per-array shard chains) still
                # load the host 'I': feed the previous layer's output
                t["I"] = np.asarray(
                    self.outputs[segment.programs[layer - 1].out_name])
            self.run_program(prog, t)
        return self.outputs

    def _run_sharded_segment(self, segment, tensors=None
                             ) -> dict[str, np.ndarray]:
        """Per-array fused execution of an M-sharded chained segment:
        each array runs its row slice of the WHOLE chain on its own
        sub-backend (fused on backends that support it), so the segment
        costs n_arrays launches instead of n_arrays * n_layers."""
        tensors = tensors or {}
        out = np.zeros((segment.m, segment.n_out), np.float32)
        for a, (fseg, (m0, m1)) in enumerate(
                zip(segment.array_segments, segment.row_ranges)):
            sub = self._shard_backend(a)
            before = sub.n_launches
            t = {k: v for k, v in tensors.items() if k != "I"}
            if "I" in tensors:
                t["I"] = np.asarray(tensors["I"])[m0:m1]
            with self._fold_bytes(sub):
                res = sub.run_segment(fseg, t)
            out[m0:m1] = np.asarray(res[fseg.out_name])[:m1 - m0]
            self.n_launches += sub.n_launches - before
        self.outputs[segment.out_name] = out
        return self.outputs

    # -- batched decode attention --------------------------------------------
    def run_batched_attention(self, programs, q: np.ndarray,
                              kT: np.ndarray, v: np.ndarray,
                              lengths=None) -> np.ndarray:
        """Advance a whole decode batch through one attention segment.

        ``programs`` is the (score, value) Program pair of a dynamic
        attention segment; ``q`` is [B, m, d] stacked per-request
        carriers, ``kT`` [B, d, skv] / ``v`` [B, skv, d_o] the
        per-request gathered KV operands, ``lengths`` the per-request
        true KV lengths.  Returns the stacked [B, m, d_o] context.

        The base implementation replays the chained Program pair once
        per request -- the sequential oracle the batched kernel must
        match.  The Programs' in-stream softmax spans the full ``skv``
        width, so the base path only accepts full-width lengths; the
        Pallas override (``kernel_ops.flash_decode``) handles genuinely
        ragged batches.
        """
        qk, pv = programs
        skv = kT.shape[2]
        if lengths is not None:
            assert all(int(x) == skv for x in np.asarray(lengths).ravel()), \
                ("base run_batched_attention replays full-width Programs; "
                 f"ragged lengths {lengths} need the Pallas backend")
        outs = []
        with self.weight_kind("kv"):
            for r in range(q.shape[0]):
                self.run_program(qk, {"I": q[r], "W": kT[r]})
                out = self.run_program(pv, {"W": v[r]})[pv.out_name]
                outs.append(np.asarray(out))
        return np.stack(outs)

    def run_batched_attention_proj(self, programs, q: np.ndarray,
                                   kT: np.ndarray, v: np.ndarray,
                                   wo: np.ndarray, *, m_out: int,
                                   k_out: int, lengths=None) -> np.ndarray:
        """Block-fused decode attention: the attention pair PLUS the
        adapt-cycled output projection ``wo`` for every request.

        The base implementation replays :meth:`run_batched_attention`
        and applies the runtime ``adapt`` + GEMM per request on the host
        -- the oracle for the Pallas override, which folds the
        projection into the decode kernel's last KV step (one launch for
        attention + Wo instead of two).  Returns [B, m_out, n_out].
        """
        from repro.runtime.executable import adapt
        ctx = self.run_batched_attention(programs, q, kT, v,
                                         lengths=lengths)
        wo = np.asarray(wo, np.float32)
        return np.stack([adapt(ctx[r], m_out, k_out) @ wo
                         for r in range(ctx.shape[0])])

    # -- multi-array execution ----------------------------------------------
    def _make_shard_backend(self, array: int) -> "Backend":
        """A fresh executor for logical array ``array`` (subclasses thread
        their construction kwargs through, and place the array's work on
        its own device)."""
        return type(self)(self.cfg)

    @contextlib.contextmanager
    def _fold_bytes(self, sub: "Backend"):
        """Count the bytes a shard executor copies, and the weight copies
        it skips, as this backend's."""
        h2d, d2h, hits = sub.n_h2d_bytes, sub.n_d2h_bytes, sub.n_weight_hits
        try:
            yield
        finally:
            self.n_h2d_bytes += sub.n_h2d_bytes - h2d
            self.n_d2h_bytes += sub.n_d2h_bytes - d2h
            self.n_weight_hits += sub.n_weight_hits - hits

    def _shard_backend(self, array: int) -> "Backend":
        be = self._shard_subs.get(array)
        if be is None:
            be = self._make_shard_backend(array)
            self._shard_subs[array] = be
        return be

    def run_sharded(self, sharded: ShardedProgram,
                    tensors: dict[str, np.ndarray] | None = None
                    ) -> dict[str, np.ndarray]:
        """Execute every shard on its array's executor and assemble.

        M/N shards write disjoint output slices; K shards produce
        partial sums combined by an explicit reduction -- the functional
        twin of the mesh all-reduce.  The hoisted epilogue activation
        (see ``program.shard_program``) runs on the assembled output.
        """
        g = sharded.base.gemm
        acc = np.zeros((g.m, g.n), np.float32)
        for shard in sharded.shards:
            sub = self._shard_backend(shard.array)
            with self._fold_bytes(sub), sub.weight_kind(self._weight_kind):
                out = np.asarray(
                    sub.run_program(shard.program,
                                    shard.slice_tensors(tensors))
                    [sharded.out_name])
            if sharded.reduce:
                acc += out
            else:
                acc[shard.m0:shard.m1, shard.n0:shard.n1] = out
        if sharded.epilogue_act is not None:
            acc = np.asarray(sharded.epilogue_act(acc))
        self.outputs[sharded.out_name] = acc
        return self.outputs

    def reset(self) -> None:
        self.outputs = {}
        for sub in self._shard_subs.values():
            sub.reset()

    def __repr__(self) -> str:  # pragma: no cover
        return (f"{type(self).__name__}(ah={self.cfg.ah}, "
                f"aw={self.cfg.aw})")
