"""A configuration's module written for the harness's tests alone: a
four-layer toy decoder, of no family the GEMM stream knows, whose check
compares logits.  ``test_bench_models.py`` registers it as
``bench.models.toy_decoder``, so the shared harness builds, records and
checks it through ``bench.models`` as it does any configuration.

The program is a small closed-loop scheduler over one jitted float32
step for all its slots.  A request's state is a decaying mean of the
embeddings of the tokens it has read, ``h <- a*h + (1-a)*E[t]``; each
layer adds ``silu(rms(x) @ U) @ D`` to ``x = h``; the logits are
``rms(x) @ E^T`` and the next token is the greedy one.  A prompt is read
one token at a time; its last token's logits give the first output.
The reference runs the same equations in float64 NumPy over each
request's whole sequence, prompt and served tokens, and reads the logits
at every position the window served.
"""

from __future__ import annotations

import dataclasses
import functools
import types

import numpy as np

FAMILY = "toy_mlp"
NUMBERS = {"logits_rel_err": "decode"}


def draw(config: dict, seed: int) -> dict[str, np.ndarray]:
    """The weights, in this order from ``default_rng(seed)``: the
    embedding, then each layer's up and down projection, each scaled by
    one over the root of its fan-in."""
    d, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    rng = np.random.default_rng(seed)
    shapes = [("embed", (v, d))]
    for layer in range(config["num_hidden_layers"]):
        shapes += [(f"up{layer}", (d, f)), (f"down{layer}", (f, d))]
    return {name: (rng.standard_normal(shape)
                   / np.sqrt(shape[0] if name != "embed" else 1.0)
                   ).astype(np.float32) for name, shape in shapes}


def prompt_ids(config: dict, seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(config["vocab_size"], size=n)


# -- the program ------------------------------------------------------------

def _step(w, h, tok, *, decay, layers):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def rms(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)

    h = decay * h + (1.0 - decay) * w["embed"][tok]
    x = h
    for layer in range(layers):
        up = jax.nn.silu(jnp.dot(rms(x), w[f"up{layer}"], precision=hi))
        x = x + jnp.dot(up, w[f"down{layer}"], precision=hi)
    return h, jnp.dot(rms(x), w["embed"].T, precision=hi)


@dataclasses.dataclass
class _Req:
    rid: int
    seed: int
    prompt_tokens: int
    decode_steps: int


@dataclasses.dataclass
class _Active:
    req: _Req
    state: np.ndarray
    last: int = 0            # the last token served
    decoded: int = 0
    status: str = "ok"


class ToyScheduler:
    """``submit`` and ``run(max_ticks)``; ``_pending`` and ``_active`` as
    the serving Scheduler keeps them."""

    def __init__(self, config: dict, slots: int, seed: int):
        import jax
        self.config, self.slots = config, slots
        self.weights = jax.device_put(draw(config, seed))
        self.step = jax.jit(functools.partial(
            _step, decay=config["state_decay"],
            layers=config["num_hidden_layers"]))
        self._pending: list[_Req] = []
        self._active: list[_Active] = []
        self._next_rid = 0

    def submit(self, decode_steps: int, seed: int, prompt_tokens: int):
        req = _Req(self._next_rid, seed, prompt_tokens, decode_steps)
        self._next_rid += 1
        self._pending.append(req)
        return req

    def tick(self, rows):
        """One step of every slot; ``rows``: (state, token) of each used
        slot.  Returns their new states and logits."""
        h = np.zeros((self.slots, self.config["hidden_size"]), np.float32)
        tok = np.zeros(self.slots, np.int32)
        for i, (state, t) in enumerate(rows):
            h[i], tok[i] = state, t
        h, logits = self.step(self.weights, h, tok)
        return np.asarray(h)[:len(rows)], np.asarray(logits)[:len(rows)]

    def _serve(self, a: _Active, logits) -> None:
        a.last = int(np.argmax(logits))
        a.decoded += 1

    def run(self, max_ticks: int = 1):
        retired = []
        for _ in range(max_ticks):
            while self._pending and len(self._active) < self.slots:
                req = self._pending.pop(0)
                a = _Active(req, np.zeros(self.config["hidden_size"],
                                          np.float32))
                for t in prompt_ids(self.config, req.seed,
                                    req.prompt_tokens):
                    (a.state,), (logits,) = self.tick([(a.state, t)])
                self._serve(a, logits)
                self._active.append(a)
            states, logits = self.tick([(a.state, a.last)
                                        for a in self._active])
            for a, state, row in zip(self._active, states, logits):
                a.state = state
                self._serve(a, row)
            for a in [a for a in self._active
                      if a.decoded >= a.req.decode_steps]:
                self._active.remove(a)
                retired.append(types.SimpleNamespace(
                    rid=a.req.rid, decode_tokens=a.decoded, status="ok"))
        return types.SimpleNamespace(requests=retired)


# -- the module's interface -------------------------------------------------

def validate(config: dict) -> None:
    if config["family"] != FAMILY or config["num_hidden_layers"] < 1:
        raise ValueError(f"not a {FAMILY} configuration: {config}")


def build(config: dict, mix: dict, seed: int) -> ToyScheduler:
    validate(config)
    return ToyScheduler(config, mix["max_concurrent"], seed)


def warm_up(sched: ToyScheduler, mix: dict) -> list[int]:
    sched.tick([])
    return [sched.slots]


class Recorder:
    """Every request's prompt seed and served tokens, and while
    ``armed`` the logits each token was served from."""

    def __init__(self, sched: ToyScheduler):
        self.sched, self.armed = sched, False
        self.served: dict[int, dict] = {}
        serve = sched._serve

        def served(a, logits):
            serve(a, logits)
            rec = self.served.setdefault(a.req.rid, {
                "seed": a.req.seed, "prompt": a.req.prompt_tokens,
                "tokens": [], "logits": {}})
            if self.armed:
                rec["logits"][len(rec["tokens"])] = np.array(logits)
            rec["tokens"].append(a.last)
        sched._serve = served

    def collect(self) -> dict:
        self.sched = None
        return self.served


def recorder(sched, config: dict, mix: dict, seed: int) -> Recorder:
    return Recorder(sched)


def reference_logits(config: dict, w: dict, ids) -> np.ndarray:
    """[len(ids), vocab]: the logits after each token, in float64."""
    def rms(x):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6)

    a = config["state_decay"]
    h = np.zeros(config["hidden_size"])
    states = []
    for t in ids:
        h = a * h + (1.0 - a) * w["embed"][t]
        states.append(h)
    x = np.stack(states)
    for layer in range(config["num_hidden_layers"]):
        up = rms(x) @ w[f"up{layer}"]
        x = x + (up / (1.0 + np.exp(-up))) @ w[f"down{layer}"]
    return rms(x) @ w["embed"].T


def readings(config: dict, mix: dict, collected: dict, *,
             weight_seed: int) -> dict:
    w = {k: v.astype(np.float64) for k, v in draw(config,
                                                  weight_seed).items()}
    errs = []
    for rec in collected.values():
        if not rec["logits"]:
            continue
        ids = list(prompt_ids(config, rec["seed"], rec["prompt"]))
        ref = reference_logits(config, w, ids + rec["tokens"])
        for j, out in rec["logits"].items():
            r = ref[rec["prompt"] - 1 + j]
            errs.append(np.linalg.norm(out - r) / np.linalg.norm(r))
    return {"logits_rel_err": float(np.max(errs))} if errs else {}


def required(limits: dict, collected) -> dict:
    return dict(limits)


def launches(sched, spans) -> dict:
    return {}
