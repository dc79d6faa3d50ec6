"""One file per backbone family, ``bench/backbones/<family>.py``, named by
a configuration's ``model.family``. Adding a family is adding its file.

A family file exports:

* ``reference(p, m, h, mm)``: the plain float32 backbone forward, from the
  wrapper's hidden states ``h`` [B, S, d_model] to the backbone's output;
  ``p`` is the backbone's weight subtree in its stored dtype, ``m`` the
  configuration's ``model`` numbers, ``mm`` ``reference.make_mm``'s
  einsum. ``mm``, ``reference._rms`` and ``reference._layer`` cast the
  weights they read to float32; a family that reads a weight any other way
  casts it where it reads it;
* ``flops(s, m)``: model FLOPs of one backbone forward over one latent of
  ``s`` tokens, counted as ``bench/flops.py`` says;
* ``ATTENTION_CAUSAL``: whether the family's attention is causal, which
  sets the flash-attention kernel's work in ``readers.attention_roofline``;
* ``STACKED``: ``{key: n}`` for each key of the weight tree below which
  every leaf carries ``n`` leading stack axes (layers, experts) ahead of
  its widths; ``bench/weights.py`` adds them up along a leaf's path and
  takes fan-in from the axes after them.

The pieces families share stay in ``bench/reference.py`` (``make_mm``,
``_rms``, ``_rope``, ``_attention``, ``_attn``, ``_mlp``, ``_layer``,
``mamba2``) and ``bench/flops.py`` (``_attn_block``, ``_mlp``,
``mamba2_layer``), for a new family to import.
"""
from __future__ import annotations

import importlib


def load(family: str):
    """The module of ``family``, imported once from this package's path."""
    name = f"{__name__}.{family}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise SystemExit(f"bench: no backbone family {family!r}: "
                         f"bench/backbones/{family}.py does not exist") from None
