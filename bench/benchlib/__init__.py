"""Shared yardstick of the chip benchmark: loading cells by name, the
compile log, traffic generation, FLOP and byte counts, the table of chip
peaks and the reduction of profiler traces.  Only ``model_config``
touches the program under test, to hand it a configuration file's
sizes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent

_modules = {}


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import a file of the benchmark by path (file names carry dots and
    dashes, which ``import`` cannot name)."""
    path = Path(path).resolve()
    if path not in _modules:
        name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab_size", "max_seq_len", "rope_theta", "norm_eps",
             "tie_embeddings", "dtype")


def model_config(config: dict):
    """The system's ``ModelConfig`` for a configuration file: its registry
    entry with every size set from the file."""
    from repro.configs import get_config
    return get_config(config["registry"]).with_(
        **{k: config[k] for k in SIZE_KEYS})
