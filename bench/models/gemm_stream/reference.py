"""The plain reference: the served GEMM stream in float32 ``jax.numpy``.

It imports nothing of the program and takes nothing the program made.
From a configuration file's published widths it builds the stream the
served path runs (one instance of each distinct layer), draws the weights
from the seed as the serving scheduler documents them, and evaluates the
stream step by step:

* a step's input is a fresh tensor when the step does not chain, else
  the previous step's output, flattened, cycle-extended and refolded to
  the step's ``[m, k]`` (the runtime's head split/merge stand-in);
* ``act(x @ w)``, with softmax over the last axis, silu for swiglu, and
  no activation where the runtime has none (squared ReLU);
* tensors are drawn in stream order (each step's weight, then its fresh
  input) as ``standard_normal`` float32 from ``numpy.random
  .default_rng(seed)``; weights and runtime K/V operands are scaled by
  ``1/sqrt(k)``;
* a request's state follows the scheduler's documented recurrence: its
  K/V starts as the decode stream's dynamic tensors drawn from the
  request seed (:func:`initial_kv`); the output of prompt chunk ``c``
  goes into time slot ``c`` and the ``j``-th decode output into slot
  ``chunks - 1 + j``, each as ``round(tanh(out), 3)`` cycled to the
  slot's width (:func:`commit`); a pass's fresh inputs are the previous
  output as ``round(out, 3)``, refolded (:func:`fresh_inputs`).

``run(..., bound=True)`` also carries each step's first-order
rounding-error bound, which scales the error of a decode state that has
grown until the softmax is sharp.

``precision="highest"`` is the reference.  ``precision="high"`` is its
control: the same stream with every product in three bf16 passes
(hi*hi + hi*lo + lo*hi, the ``Precision.HIGH`` algorithm), spelled out so
that it means the same on every platform.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: the runtime's activation for each configured MLP activation
_ACT = {"none": "none", "softmax": "softmax", "silu": "silu",
        "swiglu": "silu", "relu2": "none"}


@dataclasses.dataclass(frozen=True)
class StepSpec:
    name: str
    m: int
    k: int
    n: int
    act: str          # "none" | "softmax" | "silu"
    chained: bool
    dynamic: bool     # K^T / V: per-request runtime operand

    @property
    def fresh(self) -> bool:
        return not self.chained


def stream(config: dict, kind: str, seq_len: int) -> list[StepSpec]:
    """The served stream of one pass: ``kind`` "prefill" (``seq_len``
    tokens) or "decode" (one token against ``seq_len`` KV slots)."""
    d = config["hidden_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config.get("head_dim") or d // h
    tokens, s_q, s_kv = ((1, 1, seq_len) if kind == "decode"
                         else (seq_len, seq_len, seq_len))
    mlp_act = _ACT[config["hidden_act"]]

    def step(name, m, k, n, act="none", chained=False, dynamic=False):
        return StepSpec(name, m, k, n, act, chained, dynamic)

    steps = [step("wq", tokens, d, h * hd), step("wk", tokens, d, kv * hd),
             step("wv", tokens, d, kv * hd),
             step("qk", s_q, hd, s_kv, "softmax", True, True),
             step("pv", s_q, s_kv, hd, "none", True, True),
             step("wo", tokens, h * hd, d, chained=True)]
    if config["family"] == "moe":
        e, top = config["num_local_experts"], config["num_experts_per_tok"]
        ff = config["intermediate_size"]
        rows = max(1, tokens * top // e)
        steps += [step("moe.router", tokens, d, e),
                  step("moe.expert.up", rows, d, ff),
                  step("moe.expert.down", rows, ff, d, mlp_act, True)]
    elif config["family"] == "dense":
        ff = config["intermediate_size"]
        steps += [step("mlp.up", tokens, d, ff),
                  step("mlp.down", tokens, ff, d, mlp_act, True)]
    else:
        raise ValueError(f"no reference stream for {config['family']!r}")
    steps.append(step("lm_head", tokens, d, config["vocab_size"],
                      chained=True))
    return steps


def tensor_specs(steps: list[StepSpec]) -> list[tuple[str, tuple, str]]:
    """(name, shape, kind) in draw order; kind is weight/dynamic/input."""
    out = []
    for i, s in enumerate(steps):
        out.append((f"W{i}", (s.k, s.n), "dynamic" if s.dynamic
                    else "weight"))
        if s.fresh or i == 0:
            out.append((f"I{i}", (s.m, s.k), "input"))
    return out


def draw(steps: list[StepSpec], seed: int,
         kinds: tuple[str, ...]) -> dict[str, np.ndarray]:
    """The seeded tensors of the kinds asked for.  Every tensor up to the
    last one asked for is drawn, so the stream's order fixes each one's
    values."""
    specs = tensor_specs(steps)
    last = max(i for i, (_, _, kind) in enumerate(specs) if kind in kinds)
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape, kind in specs[:last + 1]:
        arr = rng.standard_normal(shape).astype(np.float32)
        if kind != "input":
            arr /= np.sqrt(shape[0])
        if kind in kinds:
            out[name] = arr
    return out


def stabilize(x) -> np.ndarray:
    """The recurrence's quantum: three decimals, in float32."""
    return np.round(np.asarray(x, np.float32), 3)


def fresh_inputs(steps: list[StepSpec], carry) -> dict[str, np.ndarray]:
    """The fresh inputs of a pass fed from the previous pass's output:
    ``round(out, 3)`` flattened, cycle-extended and refolded to each
    fresh step's ``[m, k]``."""
    flat = stabilize(carry).ravel()
    out = {}
    for name, shape, kind in tensor_specs(steps):
        if kind == "input":
            m, k = shape
            out[name] = np.resize(flat, m * k).reshape(m, k)
    return out


def initial_kv(decode_steps: list[StepSpec],
               req_seed: int) -> dict[str, np.ndarray]:
    """A request's K/V before its first output: the decode stream's
    dynamic tensors drawn from the request seed."""
    return draw(decode_steps, req_seed, ("dynamic",))


def commit(kv: dict[str, np.ndarray], out, pos: int) -> None:
    """Fold one served output into time slot ``pos`` of every K/V tensor
    (a tensor's time axis is its longer one)."""
    vec = stabilize(np.tanh(np.asarray(out, np.float32).ravel()))
    for arr in kv.values():
        rows, cols = arr.shape
        if cols > rows:
            arr[:, pos % cols] = np.resize(vec, rows)
        else:
            arr[pos % rows, :] = np.resize(vec, cols)


def _split_bf16(x):
    """x = hi + lo + (what two bf16 terms cannot hold)."""
    import jax.numpy as jnp
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def matmul(x, w, precision: str):
    """``x @ w`` in float32 at the given precision; x: [..., m, k]."""
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(x, w, precision=hp)
    if precision == "high":
        xh, xl = _split_bf16(x)
        wh, wl = _split_bf16(w)
        f32 = jnp.float32

        def dot(a, b):   # bf16 products, float32 sums
            return jnp.matmul(a, b, precision=hp, preferred_element_type=f32)
        return dot(xh, wh) + dot(xh, wl) + dot(xl, wh)
    raise ValueError(f"unknown precision {precision!r}")


def _act(name, y):
    import jax
    if name == "softmax":
        return jax.nn.softmax(y, axis=-1)
    if name == "silu":
        return jax.nn.silu(y)
    return y


def _adapt(x, m, k):
    """Batched twin of the runtime's shape glue: per row block, flatten,
    cycle-extend to m*k values and refold to [m, k]."""
    import jax.numpy as jnp
    b = x.shape[0]
    flat = x.reshape(b, -1)
    need = m * k
    if flat.shape[1] < need:
        reps = -(-need // flat.shape[1])
        flat = jnp.tile(flat, (1, reps))
    return flat[:, :need].reshape(b, m, k)


def _act_bound(name, pre, post, s_pre):
    """First-order bound on an activation's output error, in units of the
    unit roundoff, from the bound ``s_pre`` on its input's: the
    derivative carries the input's error, ``|post|`` adds the
    activation's own rounding."""
    import jax
    import jax.numpy as jnp
    if name == "softmax":
        ps = post * s_pre
        return ps + post * jnp.sum(ps, axis=-1, keepdims=True) + post
    if name == "silu":
        sig = jax.nn.sigmoid(pre)
        return jnp.abs(sig * (1 + pre * (1 - sig))) * s_pre + jnp.abs(post)
    return s_pre


def run(steps: list[StepSpec], weights: dict, rows: dict,
        precision: str = "highest", bound: bool = False):
    """Evaluate the stream for a batch of B requests.

    ``weights``: name -> [k, n] shared operand (host or device array).
    ``rows``: name -> [B, ...] per-request operands (fresh inputs and
    dynamic K/V).  Yields ``(index, pre_act, post_act)`` per step, each
    ``[B, m, n]`` float32 on the device.  With ``bound``, also each
    step's first-order rounding-error bound in units of the unit
    roundoff, ``(index, pre, post, bound_pre, bound_post)``: a product's
    bound is ``(|x| + bound_x) @ |w|``, its own rounding and what its
    input carries in; a fresh input and a K/V operand are exact."""
    import jax.numpy as jnp
    prev = s_prev = None
    for i, s in enumerate(steps):
        if prev is None or s.fresh:
            x = jnp.asarray(rows[f"I{i}"], jnp.float32)
            s_in = None
        else:
            x = _adapt(prev, s.m, s.k)
            s_in = _adapt(s_prev, s.m, s.k) if bound else None
        name = f"W{i}"
        w = jnp.asarray(rows[name] if s.dynamic else weights[name],
                        jnp.float32)
        pre = matmul(x, w, precision)
        post = _act(s.act, pre)
        if not bound:
            yield i, pre, post
            prev = post
            continue
        a = jnp.abs(x) if s_in is None else jnp.abs(x) + s_in
        s_pre = matmul(a, jnp.abs(w), "highest")
        s_post = _act_bound(s.act, pre, post, s_pre)
        yield i, pre, post, s_pre, s_post
        prev, s_prev = post, s_post
