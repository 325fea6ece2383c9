"""Expert parallelism: Switch-style mixture-of-experts over an `ep` mesh axis.

Net-new vs the reference (SURVEY.md §2.4 lists expert parallel as absent).
Mesh-TensorFlow-style dense dispatch: top-1 routing builds a one-hot
dispatch tensor, tokens travel to their expert's device via `lax.all_to_all`
(ICI), experts run batched FFN einsums on the MXU, results return through
the inverse all_to_all weighted by the router gate. Capacity-bounded so
every shape is static (XLA requirement); overflow tokens are dropped and
pass through the residual, exactly as in Switch Transformer.

Layout contract (inside shard_map over `ep`, n = axis size):
  x       (N_local, D)            tokens on this device
  router  (D, E)                  replicated
  w1      (E_local, D, F)         this device's experts
  w2      (E_local, F, D)
  E = n * E_local total experts.

Beside it, the share-aware top-k layer that today's sparse models use
(`moe_topk_route` + `moe_share_ffn`): sigmoid scores with a selection
bias, or softmax scores with the choice limited to the best groups of
experts, k experts a token, no capacity and no drops, and a device that is
told WHICH experts it holds and computes their part of the result alone.
On one chip it runs as it is; under an `ep` axis it is the same function
inside the exchange (tokens gathered in, parts summed out), with
`first_expert = lax.axis_index(ep) * E_local`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import current_mesh

__all__ = ["moe_dispatch", "moe_route", "moe_ffn", "moe_apply",
           "moe_topk_route", "moe_share_ffn"]


def moe_dispatch(x, router_w, num_experts, capacity, axis_name=None):
    """Top-1 routing: returns (dispatch, combine, aux_loss).

    dispatch (N, E, C) one-hot send tensor; combine = dispatch * gate.
    aux_loss is the Switch load-balancing loss (mean_frac · mean_prob · E).
    """
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                            # (N,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]

    one_hot = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)
    # position of each token within its expert's capacity buffer
    pos = jnp.cumsum(one_hot, axis=0) * one_hot - 1.0              # (N, E)
    in_cap = (pos < capacity) & (one_hot > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32)                     # (N, E, C)
    dispatch = pos_oh * in_cap[..., None]
    combine = dispatch * gate[:, None, None]

    # load-balancing aux loss (Switch eq. 4): fraction of tokens per expert
    # times mean router prob per expert, summed, scaled by E
    frac = one_hot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux_loss = num_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux_loss


def moe_route(x, router_w, num_experts):
    """Compact top-1 routing: (expert (N,) int32, pos (N,) int32, gate
    (N,) f32, aux_loss). `pos` is the token's slot within its expert's
    capacity buffer; tokens beyond capacity simply carry pos >= C and
    the fused dispatch/combine kernels drop them (same semantics as
    `moe_dispatch`'s in_cap mask, without the (N, E, C) tensor)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1).astype(jnp.int32)          # (N,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
    one_hot = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)
    # position within the expert's buffer: cumulative count of earlier
    # tokens routed to the same expert (only the chosen column is live)
    pos = (jnp.cumsum(one_hot, axis=0) * one_hot).sum(-1) \
        .astype(jnp.int32) - 1
    frac = one_hot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux_loss = num_experts * jnp.sum(frac * mean_prob)
    return expert, pos, gate, aux_loss


def _expert_ffn(buf, w1, w2, n, e_local, capacity, d_model, axis_name,
                activation):
    """The shared middle of the Switch FFN: ship each expert-shard to
    its owner, run the batched FFN einsums, ship results back. Used by
    both the einsum path and the fused-kernel path (pure code motion
    from moe_ffn — the math is unchanged)."""
    # send each expert-shard to its owner: (E, C, D) -> (n, E_local, C, D)
    buf = buf.reshape(n, e_local, capacity, d_model)
    # all_to_all over leading dim: afterwards dim 0 indexes SOURCE device,
    # and this device holds only its local experts' tokens from every peer
    buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)
    # (n, E_local, C, D): fold sources into the capacity dim for the FFN
    buf = buf.transpose(1, 0, 2, 3).reshape(e_local, n * capacity, d_model)

    h = jnp.einsum("ecd,edf->ecf", buf, w1.astype(jnp.float32))
    h = activation(h)
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(jnp.float32))

    # route back: inverse reshape + all_to_all
    out = out.reshape(e_local, n, capacity, d_model).transpose(1, 0, 2, 3)
    out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)
    return out.reshape(n * e_local, capacity, d_model)


def moe_ffn(x, router_w, w1, w2, axis_name, capacity_factor=1.25,
            activation=jax.nn.gelu):
    """Expert-parallel Switch FFN. Call INSIDE shard_map over `axis_name`.

    Shapes per the module docstring. Returns (out (N,D), aux_loss scalar —
    already psum-averaged over the axis).

    mx.kernels: with the Pallas library engaged (`kernels` knob; safe on
    any mesh — this already runs inside shard_map) the dispatch gather
    and combine scatter run as fused kernels over compact (N,) routing
    vectors (pallas_ops/moe_kernels.py) instead of materializing the
    (N, E, C) one-hot dispatch tensor in HBM. kernels=off keeps the
    einsum formulation bit-identical to the pre-kernel build.
    """
    from ..pallas_ops import moe_kernels as _mk

    n = lax.psum(1, axis_name)
    e_local = w1.shape[0]
    num_experts = n * e_local
    n_tokens, d_model = x.shape
    capacity = max(int(n_tokens * capacity_factor / num_experts), 1)

    if _mk.engaged():
        expert, pos, gate, aux = moe_route(x, router_w, num_experts)
        buf = _mk.dispatch_to_experts(x.astype(jnp.float32), expert, pos,
                                      num_experts, capacity)
        out = _expert_ffn(buf, w1, w2, n, e_local, capacity, d_model,
                          axis_name, activation)
        y = _mk.combine_from_experts(out, expert, pos, gate)
    else:
        dispatch, combine, aux = moe_dispatch(x, router_w, num_experts,
                                              capacity)
        # gather tokens into expert buffers: (E, C, D)
        buf = jnp.einsum("nec,nd->ecd", dispatch, x.astype(jnp.float32))
        out = _expert_ffn(buf, w1, w2, n, e_local, capacity, d_model,
                          axis_name, activation)
        y = jnp.einsum("nec,ecd->nd", combine, out)
    aux = lax.pmean(aux, axis_name)
    return y.astype(x.dtype), aux


def moe_apply(x, router_w, w1, w2, mesh=None, axis_name="ep",
              capacity_factor=1.25, activation=jax.nn.gelu):
    """shard_map wrapper: x (N, D) sharded on tokens, experts sharded on
    `axis_name`; router replicated. Returns (y, aux_loss)."""
    from ._compat import shard_map

    mesh = mesh or current_mesh()
    fn = shard_map(
        lambda x_, r_, w1_, w2_: moe_ffn(
            x_, r_, w1_, w2_, axis_name, capacity_factor, activation),
        mesh=mesh,
        in_specs=(P(axis_name, None), P(None, None),
                  P(axis_name, None, None), P(axis_name, None, None)),
        out_specs=(P(axis_name, None), P()),
        check_vma=False)
    return fn(x, router_w, w1, w2)


# ---------------------------------------------------------------------------
# share-aware top-k routing without drops
# ---------------------------------------------------------------------------

def moe_topk_route(x, router_w, select_bias, top_k, scale=1.0,
                   norm_topk=True, scoring="sigmoid", n_group=1,
                   topk_group=1):
    """Top-k routing over ALL experts, nothing dropped: scores =
    `scoring`(x @ router_w) in float32, "sigmoid" (`noaux_tc`) or
    "softmax" over the experts; the `top_k` experts of largest score +
    select_bias are chosen (the bias, None for none, steers the choice
    only); the gates are the chosen experts' scores, normalised over all
    `top_k` chosen when `norm_topk` and multiplied by `scale`.

    `n_group` > 1 limits the choice by group (`group_limited_greedy`):
    the experts lie in `n_group` groups of equal size (a device's experts
    in an expert-parallel deployment), a group's score is its largest
    expert score, and only experts of the `topk_group` best groups can be
    chosen, so a token is sent to at most `topk_group` devices.

    x (N, D); router_w (D, E); select_bias (E,) or None. Returns (expert
    (N, k) int32, gate (N, k) float32). Every token keeps its k experts
    whatever the load."""
    logits = jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)                        # (N, E)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"scoring {scoring!r}: sigmoid or softmax")
    choice = scores if select_bias is None \
        else scores + select_bias.astype(jnp.float32)
    if n_group > 1:
        n, e = choice.shape
        by_group = choice.reshape(n, n_group, e // n_group).max(-1)
        _, best = lax.top_k(by_group, topk_group)               # (N, g)
        kept = jax.nn.one_hot(best, n_group, dtype=jnp.bool_).any(1)
        choice = jnp.where(jnp.repeat(kept, e // n_group, axis=1),
                           choice, -jnp.inf)
    _, expert = lax.top_k(choice, top_k)
    gate = jnp.take_along_axis(scores, expert, axis=-1)
    if norm_topk:
        gate = gate / gate.sum(-1, keepdims=True)
    return expert.astype(jnp.int32), gate * scale


def moe_share_ffn(x, expert, gate, w_gate, w_up, w_down, first_expert=0):
    """This device's part of a top-k expert layer: the sum over the
    experts it HOLDS, `[first_expert, first_expert + E_held)`, of gate *
    SwiGLU expert. What experts held elsewhere would add is left out (the
    caller sums the parts under a mesh axis, or passes the partial sum on
    where it stands for one chip of a deployment).

    x (N, D); expert/gate (N, k) from `moe_topk_route`; w_gate/w_up
    (E_held, D, F); w_down (E_held, F, D). Returns (N, D) in x's dtype.

    Every held expert sees every token, with a zero gate where the token
    was not routed to it: three batched matmuls with static shapes, no
    capacity, no sort. At a decode step's token count (tens of tokens a
    chip) the experts' weights are read once either way and their bytes
    bound the time; a prefill-sized N wants a grouped matmul over tokens
    sorted by expert instead (ROADMAP Queue 2)."""
    e_held = w_gate.shape[0]
    # one_hot of an index outside [0, E_held) is a row of zeros: an
    # expert held elsewhere adds nothing here
    dense_gate = jnp.einsum(
        "nke,nk->ne", jax.nn.one_hot(expert - first_expert, e_held,
                                     dtype=jnp.float32), gate)  # (N, E_held)
    f32 = jnp.float32
    g = jnp.einsum("nd,edf->enf", x, w_gate, preferred_element_type=f32)
    u = jnp.einsum("nd,edf->enf", x, w_up, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.einsum("enf,efd->end", h, w_down, preferred_element_type=f32)
    return jnp.einsum("end,ne->nd", y, dense_gate).astype(x.dtype)
