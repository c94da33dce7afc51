"""TPU Pallas kernel for the dense-adjacency MPNN message step (the
paper's own surrogate hot-spot: §II-B runs 10^5+ MPNN inferences per
campaign batch).

messages[i] = sum_j adj[i,j] * (edge[i,j] @ h[j])

Every edge matrix meets exactly one vector, so the step is a batched
matrix-vector product: memory-bound, with no operand the MXU could
reuse.  The kernel streams the edge tensor through VMEM once.

Grid: (B, N) -- one (molecule, target atom) pair per step, so a block
holds that atom's N edge matrices (N*Hd*Hd: 256 KB in f32 at the
surrogate's N=16, Hd=64), well inside scoped VMEM with double buffering.
The wrapper folds the adjacency into the source states,
w[i,j,l] = adj[i,j] * h[j,l] (1/Hd of the edge tensor's bytes), and
hands it over with a unit second-minor axis.  Each source atom j is
then one row times a transposed edge matrix, w[j] (1,Hd) @ edge[j]^T,
batched over j, which leaves the output features on lanes: the kernel
needs no in-kernel reshape or relayout (Mosaic refuses the unit-axis
broadcast of the adjacency, ``a[:, :, None, None]``, and the lane-to-
sublane relayout a vector-unit reduction over l would need).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call


def _kernel(w_ref, e_ref, o_ref):
    w = w_ref[0, 0].astype(jnp.float32)           # (N, 1, Hd)   [j, -, l]
    e = e_ref[0, 0].astype(jnp.float32)           # (N, Hd, Hd)  [j, k, l]
    m = jax.lax.dot_general(w, e, (((2,), (2,)), ((0,), (0,))),
                            precision=jax.lax.Precision.HIGHEST)  # [j, -, k]
    o_ref[0, 0] = jnp.sum(m, axis=0).astype(o_ref.dtype)


@jax.jit
def message_pass_pallas(h, edge_mat, adj):
    """h (B,N,Hd); edge_mat (B,N,N,Hd,Hd); adj (B,N,N) -> (B,N,Hd)."""
    B, N, Hd = h.shape
    w = adj[..., None].astype(h.dtype) * h[:, None, :, :]      # (B,N,N,Hd)
    out = pallas_call(
        _kernel, w.reshape(B, N, N, 1, Hd), edge_mat,
        grid=(B, N),
        in_specs=[
            pl.BlockSpec((1, 1, N, 1, Hd), lambda b, i: (b, i, 0, 0, 0)),
            pl.BlockSpec((1, 1, N, Hd, Hd), lambda b, i: (b, i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Hd), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, 1, Hd), h.dtype),
    )
    return out.reshape(B, N, Hd)
