"""A configuration file `bench/configs/<config>.json`, its model family, and
the random weights made from the seed.

The file holds the model's published `config.json` keys as they are run,
under ``family`` the name of its module `bench/families/<family>.py`, and
under ``deployment`` the sizes the serving batcher is given. The family
module holds everything that is particular to an architecture: the keys it
requires, the program's `ModelConfig`, the parameter tree, the reference's
layer and the parameters and weight bytes a step computes and reads. The
weights are made on the device in one jitted call, in the layout the
family's program takes and in the type it serves them in. The reference
(`reference.py`) makes the same weights from the same seed with this same
function, so nothing the program made reaches it.
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent
FAMILIES = BENCH / "families"
_FAMILY_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
_loaded: dict = {}


def load_family(name: str, families_dir: Path | None = None):
    """The family module `<families_dir>/<name>.py` (`FAMILIES` by
    default), loaded once per path so that its functions keep one identity,
    which the reference's jitted forward is keyed on."""
    fdir = Path(families_dir or FAMILIES)
    path = fdir / f"{name}.py"
    if not (isinstance(name, str) and _FAMILY_NAME.fullmatch(name)
            and path.is_file()):
        known = sorted(p.stem for p in fdir.glob("*.py"))
        raise ValueError(f"family {name!r} has no module in {fdir}; "
                         f"known: {known}")
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(
            f"bench_family_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def family(c: dict):
    """The family module that configuration ``c`` names."""
    if "family" not in c:
        raise ValueError(f"config {c.get('name', '?')}: no \"family\" key; "
                         f"it names a module of {FAMILIES}")
    return load_family(c["family"])


def load_config(name: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.setdefault("name", name)
    missing = [k for k in family(cfg).REQUIRED if k not in cfg]
    if missing:
        raise ValueError(f"config {name}: missing {missing}")
    return cfg


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def model_config(c: dict):
    """The program's ModelConfig for configuration file ``c``."""
    return family(c).model_config(c)


def jax_key(seed: int):
    """A JAX key from any whole-number seed, 64-bit ones included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def _is_leaf(x) -> bool:
    """A leaf of a family's parameter tree: a shape, or ``{"shape": ...}``
    with a ``dtype`` other than the configuration's and, optionally, an
    ``init`` rule ("gain" or "normal") other than the one its name picks."""
    return isinstance(x, (list, tuple)) or (isinstance(x, dict)
                                            and "shape" in x)


def _fan_in(path: str, shape) -> int:
    if path in ("embed", "head"):
        return shape[-1] if path == "embed" else shape[0]
    return shape[-2]


@partial(jax.jit, static_argnames=("shapes_json", "dtype"))
def _make(key, shapes_json: str, dtype: str):
    shapes = json.loads(shapes_json)
    flat, tree = jax.tree.flatten_with_path(shapes, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, leaf) in zip(keys, flat):
        name = path[-1].key
        spec = leaf if isinstance(leaf, dict) else {"shape": leaf}
        shape = spec["shape"]
        init = spec.get("init", "gain" if "norm" in name else "normal")
        if init not in ("gain", "normal"):
            raise ValueError(f"leaf {name}: unknown init {init!r}")
        if init == "gain":
            # norm gains near 1, so that a gain left out shows
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            # unit-variance outputs at every width; logits of std ~1
            w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                _fan_in(name, shape))
        out.append(w.astype(spec.get("dtype", dtype)))
    return jax.tree.unflatten(tree, out)


def make_weights(c: dict, seed: int) -> dict:
    """All weights of configuration ``c`` from ``seed``, on the default
    device, in one jitted call."""
    shapes = json.dumps(family(c).param_shapes(c), sort_keys=True)
    return _make(jax_key(seed), shapes, c["torch_dtype"])
