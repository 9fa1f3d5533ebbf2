"""The one traffic generator: turns a mix file's parameters and a seed
into the requests of one run.

Every seed gets the same work: a length is the distribution's quantile
at ``(i + 0.5) / n`` for the ``i``-th of ``n`` requests.  The seed draws
every request's tensors.

Mix parameters (a JSON file under ``bench/traffic``):

``loop``
    ``"closed"``: ``sessions`` requests admitted before the window; one
    that finishes is replaced at once by the next replacement, whose
    lengths cycle through 64 quantiles.
``prompt_tokens``, ``output_tokens``
    ``{"dist": "fixed", "value"}`` or ``{"dist": "uniform", "min", "max"}``.
``max_concurrent``, ``kv_extent``
    Scheduler slots and the decode KV extent.
"""

from __future__ import annotations

import dataclasses

_SEED_MULT = 1_000_003
_REPLACEMENTS = 64


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int
    prompt_tokens: int
    output_tokens: int
    seed: int                 # the request's tensors derive from it


@dataclasses.dataclass(frozen=True)
class Plan:
    mix: dict
    seed: int
    sessions: list[RequestSpec]       # admitted during set-up

    def replacement(self, k: int) -> RequestSpec:
        """The ``k``-th session that replaces a finished one."""
        return _spec(self.mix, self.seed, len(self.sessions) + k,
                     (k % _REPLACEMENTS + 0.5) / _REPLACEMENTS)


def quantile(dist: dict, q: float) -> float:
    kind = dist["dist"]
    if kind == "fixed":
        return float(dist["value"])
    if kind == "uniform":
        return dist["min"] + (dist["max"] - dist["min"]) * q
    raise ValueError(f"unknown length distribution {kind!r}")


def _length(dist: dict, q: float) -> int:
    return max(1, round(quantile(dist, q)))


def _spec(mix: dict, seed: int, index: int, q: float) -> RequestSpec:
    return RequestSpec(index=index,
                       prompt_tokens=_length(mix["prompt_tokens"], q),
                       output_tokens=_length(mix["output_tokens"], q),
                       seed=(seed * _SEED_MULT + index) % 2**63)


def generate(mix: dict, seed: int) -> Plan:
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = mix["sessions"]
    return Plan(mix=mix, seed=seed,
                sessions=[_spec(mix, seed, i, (i + 0.5) / n)
                          for i in range(n)])
