"""Architecture modules, one file each, found by name: ``archs/<name>.py``.

A configuration file names its module under the top-level key
``"bench_arch"``; without the key it is ``dense_gqa``. A module provides:

- ``HF_KEYS``: published config key -> (program ``ModelConfig`` field,
  converter), the keys ``program_config`` reads;
- ``program_config(cfile)``: the program's ``ModelConfig`` for the file;
- ``make_params(seed, mc, shardings=None)``: the served parameter tree,
  made from the seed in one jitted call; given a tree of shardings, made
  straight into them, with the same values;
- ``logit_gaps(seed, mc, seqs, length, *, control=False)``: the plain
  float32 reference and its lower-precision control, returning what
  ``reference.logit_gaps`` returns;
- ``decode_token_flops(mc, ctx)``, ``prefill_chunk_flops(mc, start, n)``,
  ``decode_attn_cost(mc, ctxs)``, ``prefill_attn_cost(mc, rows)``: the
  operation and byte counts the per-layer readers need (as ``flops.py``);
- ``KERNELS``: ``{"decode": ..., "prefill": ...}``, the device op names of
  the decode and prefill-chunk attention kernels, which the trace is read
  by.
"""
from __future__ import annotations

import importlib
import re

DEFAULT = "dense_gqa"
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


def load(name: str):
    """The architecture module ``archs/<name>.py``."""
    if not _NAME.match(name):
        raise ValueError(f"bench_arch {name!r} is not a module name")
    return importlib.import_module(f"{__name__}.{name}")


def for_config(cfile: dict):
    """The architecture module a configuration file names."""
    return load(cfile.get("bench_arch", DEFAULT))
