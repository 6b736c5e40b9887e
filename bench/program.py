"""What the harness takes from the system under test, in one place: the
model configuration as the program names it, and the program's parameter
tree filled with the seeded weights of the configuration's reference.

A configuration file's ``reference`` key names the module that describes
its model family (``bench/reference/<family>.py``); :func:`family` loads
it, and every family-specific step of the harness goes through the names
in ``FAMILY``:

- ``arch_config(c, cfg, reduced)``: the program's configuration ``cfg``
  checked against the file ``c`` and completed from it; raises for a
  family or pattern the module does not describe;
- ``spec_of(c, cfg)``: the reference's description of that model;
- ``to_program(ref)``, ``to_reference(prog)``: the two parameter layouts;
- ``seed_key(seed)``, ``init_params(key, spec, dtype)``: the seeded
  weights, in the reference's layout;
- ``served_logit_gaps(...)``, ``train_reference(...)``, ``leaf_norms``,
  ``AdamW``: what the serve and train checks compare with;
- ``decode_bytes(c, tokens, context)``, ``decode_flops(c, tokens,
  context)``, ``prefill_flops(c, s)``, ``train_flops_per_token(c, seq)``:
  the work each step needs, for the readers;
- ``SCOPES``: the program's named scopes in this family's programs, which
  the trace reduction splits device time by.
"""
from __future__ import annotations

import importlib
import os
import sys
from functools import partial
from types import ModuleType

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs import get_config  # noqa: E402
from repro.models import transformer as T  # noqa: E402

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
FAMILY = ("arch_config", "spec_of", "to_program", "to_reference",
          "seed_key", "init_params", "served_logit_gaps", "train_reference",
          "leaf_norms", "AdamW", "decode_bytes", "decode_flops",
          "prefill_flops", "train_flops_per_token", "SCOPES")
REFERENCE_DIR = "bench/reference/"


def family(c: dict) -> ModuleType:
    """The module the configuration file ``c`` names as its reference;
    refuses a path outside ``bench/reference``, a missing module, and one
    that lacks a name of ``FAMILY``."""
    path = c["reference"]
    name = path[len(REFERENCE_DIR):-len(".py")]
    if (not path.startswith(REFERENCE_DIR) or not path.endswith(".py")
            or not name.isidentifier()
            or not os.path.isfile(os.path.join(ROOT, path))):
        raise ValueError(f"{c['name']}: its reference {path!r} is not a "
                         f"module in {REFERENCE_DIR}")
    mod = importlib.import_module(f"bench.reference.{name}")
    missing = [n for n in FAMILY if not hasattr(mod, n)]
    if missing:
        raise ValueError(f"{c['name']}: {path} does not define {missing}")
    return mod


def arch_config(c: dict, reduced: bool = False) -> T.ArchConfig:
    """The program's configuration for the configuration file ``c``, as its
    family module checks and completes it, in the precision the file
    states; refuses a tied head on the train path (the program trains its
    output head as a parameter of its own)."""
    cfg = family(c).arch_config(c, get_config(c["arch"], reduced=reduced),
                                reduced)
    if c["path"] == "train" and c["tie_word_embeddings"]:
        raise ValueError(f"{c['arch']}: the program cannot train a tied "
                         f"output head")
    return cfg.with_(param_dtype=DTYPES[c["param_dtype"]],
                     dtype=DTYPES[c["compute_dtype"]])


def make_params(c: dict, cfg: T.ArchConfig, seed: int):
    """The seeded weights in the program's layout and served type, made on
    the device in one jitted call."""
    fam = family(c)
    build = jax.jit(partial(fam.init_params, spec=fam.spec_of(c, cfg),
                            dtype=cfg.param_dtype))
    params = fam.to_program(build(fam.seed_key(seed)))
    want = T.abstract_params(jax.random.PRNGKey(0), cfg)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if shapes != jax.tree.map(lambda a: (a.shape, a.dtype), want):
        raise ValueError("the seeded weights do not fit the program's "
                         "parameter tree")
    return params
