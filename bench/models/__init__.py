"""One module per kind of configuration: how its cell is built, what the
window's answers are recorded as, and how they are checked.

A configuration file names its module under ``"model"``;
:func:`load` imports ``bench.models.<model>``.  The module provides:

``NUMBERS``
    name -> the kind of call it reads, for every number it compares.
``validate(config)``
    raises ValueError where the file departs from the program's own
    configuration.
``build(config, mix, seed)``
    the ``Scheduler`` the window drives, weights drawn from the seed.
``warm_up(sched, mix)``
    compiles every shape the mix reaches; returns what it warmed.
``recorder(sched, config, mix, seed)``
    created before the first request; its ``armed`` is set for the
    window; its ``collect()`` returns what the check needs and drops
    its hold on the scheduler.
``readings(config, mix, collected, *, weight_seed, control=False)``
    name -> number, from the plain reference; with ``control``,
    ``(program, control)``, the control being the reference in the
    precision below the configuration's in the program's place.
``required(limits, collected)``
    the limits this run is held to.
``launches(sched, spans)``
    kernel -> [(flops, bytes)] of every launch the traced window made.
"""

from __future__ import annotations

import importlib
import re


def load(config: dict):
    """The module that builds, records and checks ``config``."""
    name = config.get("model")
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z_]\w*", name):
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f"module under \"model\" (got {name!r})")
    return importlib.import_module(f"bench.models.{name}")
