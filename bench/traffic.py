"""Traffic mixes and the clients that drive them, both found by name.

A mix is a data file ``traffic/<mix>.json`` of parameters.  Its ``kind``
names the client that reads it, a module ``kinds/<kind>.py`` whose
``Client(cfg, mix, index, draw, seed)`` warms up every shape its window
uses in ``setup``, runs the window in ``run``, keeps what it measured in
``readings``, hands out a sample of what the window answered in
``sample``, and frees the program's state in ``close``.  A new mix of a
known kind is a data file alone; a new kind is a module of its own.
"""
from __future__ import annotations

import importlib.util
import json
import os

__all__ = ["annotate", "load_mix", "load_module", "make_client"]

HERE = os.path.dirname(os.path.abspath(__file__))
# Seconds past the window's close that answers are waited for.
DRAIN_S = 60.0


def load_module(directory: str, name: str):
    """The module ``<directory>/<name>.py``, loaded from its path."""
    path = os.path.join(directory, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no {path}")
    spec = importlib.util.spec_from_file_location(
        os.path.basename(directory) + "_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def annotate(name: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def make_client(cfg, mix, index, draw, seed):
    kind = load_module(os.path.join(HERE, "kinds"), mix["kind"])
    return kind.Client(cfg, mix, index, draw, seed)
