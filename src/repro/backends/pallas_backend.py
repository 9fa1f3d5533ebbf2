"""PallasBackend: compile a lowered Program to ``pl.pallas_call``.

The interpreter replays the MINISA instruction stream tile by tile; this
backend instead *compiles* the Program once and runs the whole tile lattice
as a single Pallas kernel launch per layer -- the paper's (mapping, layout)
co-switching decisions executed at hardware speed:

  Program tiling (M_t, K_t, N_t)  ->  kernel grid (n_m, n_n, n_k) and
                                      (bm, bk, bn) BlockSpecs, K innermost
                                      sequential (the OB revisit order)
  SetOVNLayout / IO-S dataflow    ->  the accumulator is transposed w.r.t.
                                      the host output, which lowers to the
                                      BIRRD-style ``out_block_t`` output
                                      index map (blocks stored transposed
                                      at swapped coordinates, i.e. the free
                                      output re-layout in the reduction)
  operand residency               ->  block shapes: a ``full``/``panel``
                                      resident operand keeps its Program
                                      tile extent; ``tiled`` operands are
                                      additionally clamped to
                                      ``max_block`` so one kernel block
                                      never exceeds a VMEM-sized working
                                      set (the §IV-G sub-tiling analogue)
  elementwise Activation drain    ->  fused into the final-K store
                                      (``kernels.nest_gemm.ACT_FNS``);
                                      row-wise activations are applied by
                                      the backend on the assembled output,
                                      in the accumulator orientation the
                                      interpreter uses
  same-shaped tile runs           ->  one ``pallas_call`` covers the whole
                                      lattice; ragged edge tiles become
                                      zero-padding (the paper's implicit
                                      zero-pad semantics), not extra
                                      launches

On CPU the kernel runs in Pallas interpret mode (semantics-exact); on TPU
the identical call sites lower to Mosaic.  Chained Programs resolve their
elided/retargeted inputs against the backend's previous outputs, mirroring
the machine's on-chip commit.

Fused segments: ``run_segment`` compiles a whole ``program.chain``-ed
segment (a :class:`~repro.core.program.FusedSegment`) to ONE
``pallas_call`` -- the chained activation stays resident in VMEM scratch
across layers, each layer's weight streams in host-K tiles against it,
and each layer's Activation drain fuses at its final-K store
(``kernels.fused_chain``).  One fused compile replaces one compile per
GEMM, and the intermediate HBM round trips the per-layer path pays
vanish structurally.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import TYPE_CHECKING, Any

import jax
import numpy as np

from repro.backends.base import Backend
from repro.core import isa
from repro.core import program as programlib
from repro.kernels import nest_gemm as nglib
from repro.kernels import ops as kernel_ops
from repro.obs import metrics as obs_metrics
from repro.obs.trace import trace

#: every pallas_call site increments this (labelled by kernel and by the
#: device it ran on), so the scheduler's per-instance ``n_launches``
#: diffs and the process-wide scrape agree on what actually launched
_LAUNCHES = obs_metrics.counter(
    "backend_launches_total", "pallas_call launches by kernel and device")
_WEIGHT_HITS = obs_metrics.counter(
    "backend_resident_weight_hits_total",
    "weight operands found resident on the device, so not uploaded")
_RESIDENT_BYTES = obs_metrics.gauge(
    "backend_resident_weight_bytes",
    "bytes of static weights a backend keeps on its device")

#: bound on the resident weights where the device reports no memory
#: limit (the CPU backend)
_RESIDENT_FALLBACK_BYTES = 1 << 30

if TYPE_CHECKING:  # pragma: no cover
    from repro.configs.feather import FeatherConfig
    from repro.core.program import Program


@dataclasses.dataclass(frozen=True)
class CompiledProgram:
    """Compilation artifact: everything needed to launch the kernel."""
    wos: bool                    # WO-S (host-oriented) vs IO-S (transposed)
    bm: int                      # host-coordinate kernel block sizes
    bk: int
    bn: int
    grid: tuple[int, int, int]   # (m blocks, n blocks, k blocks), padded
    out_block_t: bool            # BIRRD-style transposed-block output map
    fused_act: str | None        # activation fused into the kernel
    host_act: Any                # activation applied post-assembly
    input_name: str | None       # Load tensor name of the input operand
    weight_name: str             # Load tensor name of the weight operand
    out_name: str
    commit: bool                 # final Write commits on-chip (chaining)
    residency: dict[str, str]

    @property
    def n_launches(self) -> int:
        """Kernel grid cells in the single launch (vs Program tiles)."""
        return self.grid[0] * self.grid[1] * self.grid[2]

    def describe(self) -> dict:
        return {
            "dataflow": "WOS" if self.wos else "IOS",
            "blocks": (self.bm, self.bk, self.bn),
            "grid": self.grid,
            "out_block_t": self.out_block_t,
            "fused_act": self.fused_act,
            "residency": dict(self.residency),
        }


@dataclasses.dataclass(frozen=True)
class CompiledSegment:
    """Fused-segment artifact: ONE kernel launch for a chained segment.

    Mirrors the :class:`~repro.core.program.FusedSegment` geometry with
    the backend's ``max_block`` clamp applied; ``dims`` are the per-layer
    TRUE host (M, K, N) extents the streamed launch binds, ``adapts``
    the in-kernel shape-glue boundaries.
    """
    bm: int                         # resident-activation rows per grid step
    layer_bks: tuple[int, ...]      # per-layer weight K-streaming tile
    acts: tuple[str | None, ...]    # per-layer in-kernel activation
    dims: tuple[tuple[int, int, int], ...]
    adapts: tuple[bool, ...]
    out_name: str

    @property
    def n_layers(self) -> int:
        return len(self.dims)

    def describe(self) -> dict:
        return {
            "n_layers": self.n_layers,
            "bm": self.bm,
            "layer_bks": self.layer_bks,
            "acts": self.acts,
            "dims": self.dims,
            "adapts": self.adapts,
        }


def compile_segment(segment, *, max_block: int = 2048) -> CompiledSegment:
    """Clamp the FusedSegment launch geometry to the backend's working-set
    bound.  One call == one fused compile (vs one per layer unfused).

    Adapt-crossing segments keep their bm unclamped: the in-kernel slab
    permutation needs every activation row resident in one M block.
    """
    from repro.kernels.fused_chain import FUSED_ACT_FNS
    for act in segment.acts:
        if act is not None and act not in FUSED_ACT_FNS:
            raise ValueError(f"activation {act!r} has no fused kernel")
    adapts = tuple(segment.adapts)
    bm = segment.bm if any(adapts) else max(1, min(segment.bm, max_block))
    return CompiledSegment(
        bm=bm,
        layer_bks=tuple(max(1, min(bk, max_block))
                        for bk in segment.layer_bks),
        acts=tuple(segment.acts),
        dims=tuple((p.gemm.m, p.gemm.k, p.gemm.n)
                   for p in segment.programs),
        adapts=adapts,
        out_name=segment.out_name)


def _load_names(program: "Program") -> tuple[str | None, str]:
    """Tensor names the Program's Loads bind to ('I' may be retargeted to a
    producer's committed output, or absent entirely when elided)."""
    input_name, weight_name = None, "W"
    for tile in program.tiles:
        for op in tile.loads:
            if op.meta.get("operand") == "I":
                input_name = op.meta["tensor"]
            elif op.meta.get("operand") == "W":
                weight_name = op.meta["tensor"]
    return input_name, weight_name


def compile_program(program: "Program", *,
                    max_block: int = 2048) -> CompiledProgram:
    """Derive the kernel launch geometry from the Program's tiling."""
    cfg = program.cfg
    snapped = programlib.snap_tiling(program.gemm, program.choice, cfg)
    if snapped is None:  # lower() would have raised already
        raise ValueError(f"infeasible program {program.choice}")
    m_t, k_t, n_t = snapped
    wos = program.choice.df == isa.Dataflow.WOS
    # search orientation -> host orientation: under IO-S the search m-rank
    # tiles host N and the search n-rank tiles host M
    if wos:
        bm_t, bk_t, bn_t = m_t, k_t, n_t
    else:
        bm_t, bk_t, bn_t = n_t, k_t, m_t

    def _block(tile_ext: int, dim: int, mode: str) -> int:
        b = min(tile_ext, dim)
        if mode == programlib.TILED:
            b = min(b, max_block)
        return max(1, min(b, max_block * 2))

    g = program.gemm
    sta_mode = program.residency["stationary"]
    str_mode = program.residency["streaming"]
    # host-M is streamed under WO-S, stationary under IO-S (and vice versa
    # for host-N); K follows the tighter of the two operands
    bm = _block(bm_t, g.m, str_mode if wos else sta_mode)
    bn = _block(bn_t, g.n, sta_mode if wos else str_mode)
    bk = _block(bk_t, g.k,
                programlib.TILED if (sta_mode == programlib.TILED
                                     or str_mode == programlib.TILED)
                else programlib.FULL)
    grid = (math.ceil(g.m / bm), math.ceil(g.n / bn), math.ceil(g.k / bk))

    fused = None
    host_act = None
    if program.activation is not None:
        if program.act_name in nglib.ACT_FNS:
            fused = program.act_name
        else:
            host_act = program.activation

    input_name, weight_name = _load_names(program)
    commit = any(op.meta.get("commit_to") is not None
                 for tile in program.tiles for op in tile.drains)
    return CompiledProgram(
        wos=wos, bm=bm, bk=bk, bn=bn, grid=grid,
        out_block_t=not wos, fused_act=fused, host_act=host_act,
        input_name=input_name, weight_name=weight_name,
        out_name=program.out_name, commit=commit,
        residency=dict(program.residency))


def _static(x) -> bool:
    """A host array whose values cannot change under the backend: fp32
    (uploaded as it is, not as a fresh copy), read-only, and owning its
    data, so no writeable base aliases it."""
    return (isinstance(x, np.ndarray) and x.dtype == np.float32
            and not x.flags.writeable and x.flags.owndata)


def _resident_bound(device) -> int:
    """Bytes of weights a backend keeps resident: half the device's
    memory limit, or a fixed bound where the device reports none."""
    d = device if device is not None else jax.devices()[0]
    limit = (d.memory_stats() or {}).get("bytes_limit")
    return limit // 2 if limit else _RESIDENT_FALLBACK_BYTES


class PallasBackend(Backend):
    """Compiled execution: one Pallas kernel launch per Program."""

    name = "pallas"

    def __init__(self, cfg: "FeatherConfig", *, interpret: bool | None = None,
                 max_block: int = 2048, compile_cache=None, device=None):
        super().__init__(cfg)
        # interpret=None: Python-interpret on the CPU backend only
        self.interpret = kernel_ops.interpret_mode(interpret)
        self.max_block = max_block
        #: where this backend's launches run (None: JAX's default device);
        #: a per-array shard backend is pinned to its array's device
        self.device = device
        self._committed: np.ndarray | None = None
        # id(program) alone would go stale once a Program is collected and
        # its id reused; keeping the Program alongside pins the id and lets
        # us verify the hit.  Bounded so a long-lived backend cannot leak.
        self._cache: dict[int, tuple["Program", CompiledProgram]] = {}
        self._fused_cache: dict[int, tuple[Any, CompiledSegment]] = {}
        self._cache_limit = 128
        # Optional shared artifact store (runtime.cache.ProgramCache):
        # keyed *structurally*, so fresh-but-equivalent Program objects
        # (a rebuilt executable, another backend instance) reuse compiled
        # artifacts instead of recompiling.  n_compiles counts the real
        # compile_program invocations this instance performed.
        self.compile_cache = compile_cache
        self.n_compiles = 0
        # kernel launches this instance performed (one pallas_call each);
        # the Scheduler diffs this to prove one-launch-per-segment ticks
        self.n_launches = 0
        # static weights kept on the device across launches (see _put):
        # id(host) -> (host, device array), least recently used first.
        # The entry pins the host array, so its id cannot be reused while
        # the entry lives.  The byte bound is read from the device on the
        # first upload.
        self._resident: collections.OrderedDict[
            int, tuple[np.ndarray, jax.Array]] = collections.OrderedDict()
        self._resident_bytes = 0
        self._resident_limit: int | None = None

    def compile(self, program: "Program") -> CompiledProgram:
        key = id(program)
        hit = self._cache.get(key)
        if hit is not None and hit[0] is program:
            return hit[1]
        comp = None
        if self.compile_cache is not None:
            comp = self.compile_cache.lookup_compiled(program,
                                                      self.max_block)
        if comp is None:
            with trace.span("backend.compile", out=program.out_name):
                comp = compile_program(program, max_block=self.max_block)
            self.n_compiles += 1
            if self.compile_cache is not None:
                self.compile_cache.store_compiled(program, self.max_block,
                                                  comp)
        if len(self._cache) >= self._cache_limit:
            self._cache.pop(next(iter(self._cache)))
        self._cache[key] = (program, comp)
        return comp

    def compile_fused(self, segment) -> CompiledSegment:
        """Fused-tier compile: one artifact per segment (structural key
        via the shared ProgramCache when attached), so serving a
        multi-layer cell costs ONE compile where the per-layer path pays
        one per GEMM."""
        key = id(segment)
        hit = self._fused_cache.get(key)
        if hit is not None and hit[0] is segment:
            return hit[1]
        comp = None
        if self.compile_cache is not None:
            comp = self.compile_cache.lookup_fused(segment, self.max_block)
        if comp is None:
            with trace.span("backend.compile_fused",
                            n_layers=len(segment.programs)):
                comp = compile_segment(segment, max_block=self.max_block)
            self.n_compiles += 1
            if self.compile_cache is not None:
                self.compile_cache.store_fused(segment, self.max_block,
                                               comp)
        if len(self._fused_cache) >= self._cache_limit:
            self._fused_cache.pop(next(iter(self._fused_cache)))
        self._fused_cache[key] = (segment, comp)
        return comp

    def run_segment(self, segment, tensors=None):
        """ONE ``pallas_call`` for the whole chained segment: each
        layer's weight streams HBM->VMEM in double-buffered K tiles
        against the resident activation slab, with adapt (head-split)
        boundaries lowered to in-kernel slab permutations; only the
        segment input, the weight tiles and the final output cross HBM.

        A :class:`~repro.core.program.ShardedFusedSegment` dispatches to
        the per-array path (one fused launch per array).
        """
        if isinstance(segment, programlib.ShardedFusedSegment):
            return self._run_sharded_segment(segment, tensors)
        comp = self.compile_fused(segment)
        self._count_launch("fused_chain")
        tensors = tensors or {}
        x = self._resolve("I", tensors, False)
        ws = [self._resolve(f"W{layer}", tensors, False)
              for layer in range(comp.n_layers)]
        with trace.span("launch", kernel="fused_chain",
                        n_layers=comp.n_layers, bm=comp.bm,
                        out=comp.out_name) as sp:
            xd = self._put(x, "input")
            wds = [self._put(w, "weight") for w in ws]
            out = self._finish("fused_chain", lambda: kernel_ops.fused_chain(
                xd, wds, bm=comp.bm, bks=comp.layer_bks, acts=comp.acts,
                adapts=comp.adapts, dims=comp.dims,
                interpret=self.interpret, out_dtype=jax.numpy.float32))
            if sp:
                sp.set(vmem_highwater_bytes=getattr(
                    segment, "vmem_highwater_bytes", lambda: None)())
        self.outputs[comp.out_name] = out
        return self.outputs

    def run_batched_attention(self, programs, q, kT, v, lengths=None):
        """ONE ``flash_decode`` launch for the whole decode batch: every
        request's score+context GEMM pair, each row masked to its own
        true KV length (SNIPPETS §2 flash-decode shape).  Replaces 2*B
        per-request launches with one."""
        self._count_launch("flash_decode")
        with trace.span("launch", kernel="flash_decode",
                        batch=int(q.shape[0])):
            k = self._put(kT, "kv").transpose(0, 2, 1)
            qd, vd = self._put(q, "input"), self._put(v, "kv")
            return self._finish(
                "flash_decode", lambda: kernel_ops.flash_decode(
                    qd, k, vd, lengths, interpret=self.interpret))

    def run_batched_attention_proj(self, programs, q, kT, v, wo, *,
                                   m_out, k_out, lengths=None):
        """ONE ``flash_decode_proj`` launch: batched ragged attention
        with the output projection folded into the last KV step, the
        adapt head-merge done as a static in-VMEM permutation.  Replaces
        the attention launch plus B per-request Wo launches."""
        self._count_launch("flash_decode_proj")
        with trace.span("launch", kernel="flash_decode_proj",
                        batch=int(q.shape[0])):
            k = self._put(kT, "kv").transpose(0, 2, 1)
            qd, vd = self._put(q, "input"), self._put(v, "kv")
            wod = self._put(wo, "weight")
            return self._finish(
                "flash_decode_proj", lambda: kernel_ops.flash_decode_proj(
                    qd, k, vd, wod, lengths, m_out=m_out, k_out=k_out,
                    interpret=self.interpret))

    def _resolve(self, name: str | None, tensors, elided: bool):
        if name is None:
            if not elided or self._committed is None:
                raise KeyError("Program has no input Load and no committed "
                               "producer output to elide from")
            return self._committed
        src = tensors.get(name) if tensors else None
        if src is None:
            src = self.outputs.get(name)
        if src is None:
            raise KeyError(f"Load refers to unknown tensor {name!r}")
        return np.asarray(src)

    def _count_launch(self, kernel: str, device: str | None = None) -> None:
        self.n_launches += 1
        _LAUNCHES.inc(1, kernel=kernel, device=device or self._device_label)

    def _put(self, x, operand: str) -> jax.Array:
        """Host operand -> fp32 device array on this backend's device.

        A ``weight`` that cannot change (:func:`_static`) is uploaded
        once and kept resident: later calls with the same array return
        the device copy, count a hit and open no span.  Everything else
        is uploaded on every call.  Under tracing the ``backend.put``
        span waits for the transfer, so it times the upload;
        ``operand`` is ``input``, ``weight`` or ``kv``."""
        if operand == "weight" and _static(x):
            hit = self._resident.get(id(x))
            if hit is not None and hit[0] is x:
                self._resident.move_to_end(id(x))
                self.n_weight_hits += 1
                _WEIGHT_HITS.inc(1, device=self._device_label)
                return hit[1]
            arr = self._upload(x, operand)
            self._keep(x, arr)
            return arr
        return self._upload(np.asarray(x, np.float32), operand)

    def _upload(self, host: np.ndarray, operand: str) -> jax.Array:
        self.n_h2d_bytes += host.nbytes
        with trace.span("backend.put") as sp:
            arr = jax.device_put(host, self.device)
            if sp:
                sp.set(bytes=host.nbytes, operand=operand)
                arr.block_until_ready()
        return arr

    def _keep(self, host: np.ndarray, arr: jax.Array) -> None:
        """Make ``arr`` resident for ``host``, evicting the least
        recently used weights past the byte bound (an evicted weight is
        uploaded again on its next use)."""
        if self._resident_limit is None:
            self._resident_limit = _resident_bound(self.device)
        if host.nbytes > self._resident_limit:
            return
        self._resident[id(host)] = (host, arr)
        self._resident_bytes += host.nbytes
        while self._resident_bytes > self._resident_limit:
            _, (old, _) = self._resident.popitem(last=False)
            self._resident_bytes -= old.nbytes
        _RESIDENT_BYTES.set(self._resident_bytes, device=self._device_label)

    @property
    def _device_label(self) -> str:
        return str(self.device or "default")

    def _finish(self, kernel: str, dispatch) -> np.ndarray:
        """Run ``dispatch`` (one kernel launch) and copy its output to
        the host.  Under tracing ``backend.wait`` ends when the device
        is done and ``backend.fetch`` times the device-to-host copy."""
        with trace.span("backend.wait", kernel=kernel) as sp:
            y = dispatch()
            if sp:
                y.block_until_ready()
        with trace.span("backend.fetch") as sp:
            out = np.asarray(y)
            if sp:
                sp.set(bytes=out.nbytes)
        self.n_d2h_bytes += out.nbytes
        return out

    def _make_shard_backend(self, array: int) -> "PallasBackend":
        devices = jax.devices()
        return PallasBackend(self.cfg, interpret=self.interpret,
                             max_block=self.max_block,
                             compile_cache=self.compile_cache,
                             device=devices[array % len(devices)])

    def run_sharded(self, sharded, tensors=None):
        """One ``shard_map``-wrapped kernel launch over the array mesh.

        When the logical arrays are backed by JAX devices
        (``ArrayMesh.jax_mesh()``), the whole mesh executes as a single
        ``shard_map`` around the same ``nest_gemm`` kernel the unsharded
        path compiles: the split rank is padded to an even per-array
        extent (the paper's implicit zero-padding -- zero rows/cols/k
        contribute nothing), operands get the axis-appropriate
        PartitionSpecs, and a K split closes with ``lax.psum`` over the
        array axis.  Without a device mesh, falls back to the base
        sequential per-shard path (identical numerics).
        """
        jmesh = sharded.mesh.jax_mesh()
        if jmesh is None or sharded.n_shards < 2:
            return super().run_sharded(sharded, tensors)
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        # every shard shares one mapping choice, so the first shard's
        # compiled geometry serves the whole mesh (ragged final shards
        # are zero-padded to the uniform per-array extent)
        comp = self.compile(sharded.shards[0].program)
        g = sharded.base.gemm
        x = self._resolve(comp.input_name or "I", tensors, False)
        w = self._resolve(comp.weight_name, tensors, False)
        n = sharded.mesh.n_arrays
        axis, ax_name = sharded.axis, sharded.mesh.axis_name
        dim = {"m": g.m, "n": g.n, "k": g.k}[axis]
        pad = -dim % (-(-dim // n) * n)

        if axis == "m":
            x = np.pad(x, ((0, pad), (0, 0)))
            in_specs = (P(ax_name, None), P(None, None))
            out_spec = P(ax_name, None)
        elif axis == "n":
            w = np.pad(w, ((0, 0), (0, pad)))
            in_specs = (P(None, None), P(None, ax_name))
            out_spec = P(None, ax_name)
        else:
            x = np.pad(x, ((0, 0), (0, pad)))
            w = np.pad(w, ((0, pad), (0, 0)))
            in_specs = (P(None, ax_name), P(ax_name, None))
            out_spec = P()

        def body(xs, ws):
            o = kernel_ops.nest_gemm(
                xs, ws, bm=comp.bm, bn=comp.bn, bk=comp.bk,
                interpret=self.interpret, out_dtype=jnp.float32,
                out_block_t=comp.out_block_t, act=comp.fused_act)
            if comp.out_block_t:
                o = o.T
            if axis == "k":
                o = jax.lax.psum(o, ax_name)
            return o

        # check_vma=False: jax has no varying-axes rule for pallas_call
        self._count_launch("nest_gemm_shard_map", device="mesh")
        with trace.span("launch", kernel="nest_gemm_shard_map",
                        n_arrays=n, axis=axis, out=sharded.out_name):
            xd, wd = self._put(x, "input"), self._put(w, self._weight_kind)
            out = self._finish("nest_gemm_shard_map", lambda: jax.shard_map(
                body, mesh=jmesh, in_specs=in_specs, out_specs=out_spec,
                check_vma=False)(xd, wd))
            out = np.ascontiguousarray(out[:g.m, :g.n])
        if comp.host_act is not None:
            # per-shard Programs only keep shard-local activations (see
            # shard_program), so host application on the assembled output
            # is exact
            out = np.asarray(comp.host_act(out))
        if sharded.epilogue_act is not None:
            out = np.asarray(sharded.epilogue_act(out))
        self.outputs[sharded.out_name] = out
        return self.outputs

    def run_program(self, program: "Program",
                    tensors: dict[str, np.ndarray] | None = None
                    ) -> dict[str, np.ndarray]:
        if isinstance(program, programlib.ShardedProgram):
            return self.run_sharded(program, tensors)
        comp = self.compile(program)
        self._count_launch("nest_gemm")
        x = self._resolve(comp.input_name, tensors, program.input_elided)
        w = self._resolve(comp.weight_name, tensors, False)
        with trace.span("launch", kernel="nest_gemm", grid=comp.grid,
                        out=comp.out_name):
            xd, wd = self._put(x, "input"), self._put(w, self._weight_kind)
            out = self._finish("nest_gemm", lambda: kernel_ops.nest_gemm(
                xd, wd, bm=comp.bm, bn=comp.bn, bk=comp.bk,
                interpret=self.interpret, out_dtype=jax.numpy.float32,
                out_block_t=comp.out_block_t, act=comp.fused_act))
        if comp.host_act is not None:
            # under IO-S in the kernel's (search-oriented) orientation,
            # as the interpreter applies it
            with trace.span("host.act"):
                out = np.asarray(comp.host_act(out))
        if comp.out_block_t:
            # the kernel stored the IO-S accumulator; the final Write's
            # host-facing view is its transpose
            out = np.ascontiguousarray(out.T)
        self.outputs[comp.out_name] = out
        if comp.commit:
            self._committed = out
        return self.outputs

    def reset(self) -> None:
        super().reset()
        self._committed = None
        self._cache = {}
        self._fused_cache = {}
        self._resident.clear()
        self._resident_bytes = 0
        _RESIDENT_BYTES.set(0, device=self._device_label)
