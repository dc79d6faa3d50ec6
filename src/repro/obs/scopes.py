"""The backbone's named-scope vocabulary, and reading it back from HLO.

The models put their parts under ``jax.named_scope`` names from
:data:`SCOPES`; a scope adds no jaxpr equation and no device work, but each
compiled op keeps the path in its ``op_name`` metadata
(``jit(round)/.../while/body/.../mlp/dot_general``). An op belongs to the
innermost scope of the vocabulary on that path:

* the DiT block: ``norm``, ``attn`` (q/k/v projection, RoPE, attention,
  out projection and its residual add), ``mlp``;
* a Mamba2 layer: ``mamba2.in_proj``, ``mamba2.conv`` (conv, SiLU, split),
  ``mamba2.scan`` (softplus and decay through the chunked scan or its
  kernel, and ``y``), ``mamba2.out`` (gated norm and out projection);
  Zamba2's ``shared_block`` holds its own ``norm``/``attn``/``mlp``;
* the dropless MoE layer (``models/moe.py`` ``dropless_moe``):
  ``moe.router`` (the float32 router matmul, sigmoid scores, top-k and
  the gate weights), ``moe.dispatch`` (the sort of the (token,
  expert) rows by expert, group sizes, the gather of the rows, the
  un-permute, the gate-weighted combine and the layer's residual add),
  ``moe.experts`` (the two grouped expert matmuls and relu^2 between),
  ``moe.shared`` (the shared expert); the Nemotron-H family's Mamba2 and
  attention layers use ``mamba2.*`` and ``attn``. On the TPU the grouped
  matmuls become ops named ``ragged-dot-*`` whose ``op_name`` has lost the
  path; :data:`LOWERED` charges them to ``moe.experts``;
* the diffusion wrapper: ``wrapper.in`` (latent in-projection and time
  embedding), ``wrapper.out`` (output norm and projection);
* ``chords.step``: a whole CHORDS round (``serve/executor.py``'s round
  program), so the solver step, rectification, accept and dead-slot
  freezing — everything in it but the drift;
* ``drift``: the drift call inside the round, so its ops in none of the
  backbone's scopes (residual adds, casts) are not charged to the solver.

Compilation fuses ops, and a fusion can hold ops of two parts (a norm
folded into a matmul's prologue, the tail of one Mamba2 step into the
next). It is charged whole to one: the scope of its called computation's
root. :func:`hlo_op_scopes` marks a fusion ``mixed`` when it holds ops of
two scopes neither of which holds the other, so a reader can say how much
of the split rests on that choice; ops of an enclosing scope (the layer
scan's weight slices under ``drift``, the shared block's projections) do
not make it so.
"""
from __future__ import annotations

import re
from typing import Dict, NamedTuple, Optional

SCOPES = ("norm", "attn", "mlp", "mamba2.in_proj", "mamba2.conv",
          "mamba2.scan", "mamba2.out", "shared_block", "moe.router",
          "moe.dispatch", "moe.experts", "moe.shared", "wrapper.in",
          "wrapper.out", "chords.step", "drift")

# ops whose op_name a TPU compiler pass replaces with its own, dropping the
# scope path: XLA lowers ``jax.lax.ragged_dot``, which only the dropless
# MoE's expert matmuls call, to ops named ``ragged-dot-*``
LOWERED = (("ragged-dot-", "moe.experts"),)

_TOKEN = re.compile(r"[/()]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+)")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


class OpScope(NamedTuple):
    scope: Optional[str]  # innermost vocabulary scope; a fusion: its root's
    type: str             # result type, the op's text after " = " to a space
    mixed: bool           # a fusion holding ops of two unnested scopes


def _chain(op_name: str) -> tuple:
    """The vocabulary scopes on an ``op_name`` path, outermost first."""
    return tuple(t for t in _TOKEN.split(op_name) if t in SCOPES)


def scope_of(op_name: str) -> Optional[str]:
    """Innermost vocabulary scope on an ``op_name`` path, or None; an op
    that a compiler pass named after itself takes :data:`LOWERED`'s scope."""
    chain = _chain(op_name)
    if chain:
        return chain[-1]
    return next((scope for prefix, scope in LOWERED
                 if op_name.startswith(prefix)), None)


def _unnested(chains: set) -> bool:
    """Two of the chains end in scopes neither of which holds the other."""
    return any(a[-1] not in b and b[-1] not in a
               for a in chains for b in chains)


def hlo_op_scopes(hlo_text: str) -> Dict[str, OpScope]:
    """Op name (``fusion.139``, no ``%``) -> :class:`OpScope`, for every
    instruction in a compiled module's text
    (``jit(f).lower(...).compile().as_text()``). An op without ``op_name``
    metadata of its own, such as a fusion, takes the one of its called
    computation's root (or of its first op that has one)."""
    own: Dict[str, tuple] = {}
    roots: Dict[str, str] = {}
    chains: Dict[str, set] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            name_m = _OP_NAME.search(line)
            calls_m = _CALLS.search(line)
            op_name = name_m.group(1) if name_m else None
            own[m.group(1)] = (op_name, m.group(2),
                               calls_m.group(1) if calls_m else None)
            chain = _chain(op_name) if op_name else ()
            if chain:
                chains.setdefault(comp, set()).add(chain)
            if op_name and (line.lstrip().startswith("ROOT")
                            or comp not in roots):
                roots[comp] = op_name
            continue
        m = _HEADER.match(line)
        if m:
            comp = m.group(1)
    return {name: OpScope(scope_of(op_name or roots.get(calls, "")), rtype,
                          _unnested(chains.get(calls, set())))
            for name, (op_name, rtype, calls) in own.items()}
