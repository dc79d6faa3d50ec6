"""Grouped expert matmuls of the dropless MoE layer: least time of their
FLOPs at the bf16 peak (or of their bytes at the HBM peak, whichever is
larger) over their summed device time. ``jax.lax.ragged_dot`` lowers on
the v5e to XLA's own ragged-dot kernel, one op per matmul named
``%ragged-dot-none`` (the ``%ragged-dot-metadata`` op before them is not a
call); each does ``2 * lanes * tokens * experts_per_tok * d_model * d_ff``
FLOPs whatever the routing, since no row is dropped
(``bench/backbones/nemotron_h.py`` ``expert_call``)."""
import backbones
from readers import _kernel_share

KERNELS = {"expert_matmul": "%ragged-dot-none"}


def read(run):
    m, tr = run["model"], run["traffic"]
    work = getattr(backbones.load(m["family"]), "expert_call", None)
    if work is None:
        return None
    lanes = tr["num_slots"] * tr["num_cores"] * tr["latent_shape"][0]
    return _kernel_share(run, "expert_matmul",
                         work(lanes, tr["latent_shape"][-2], m))
