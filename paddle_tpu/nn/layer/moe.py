"""Mixture-of-Experts layer with expert parallelism over an `ep` mesh axis.

API parity: paddle.incubate.distributed.models.moe.MoELayer (later-era; the
reference snapshot predates MoE entirely — this is part of the TPU build's
first-class distributed surface, needed for expert-parallel shardings).

TPU-native (GShard/Switch style, single SPMD program): tokens are routed with
a dense top-k gate into per-expert capacity buffers via one-hot dispatch
einsums (MXU-friendly, no scatters); the stacked expert weights [E, ...]
carry a PartitionSpec over `ep`, so under jit on an ep mesh XLA turns the
dispatch einsum into the all-to-all the GPU frameworks hand-code.
Over-capacity tokens are dropped (combine weight zero), matching GShard.

``DroplessMoE`` below is the other routing: the k largest of a softmax
router, no capacity, every assignment computed by a grouped product over
rows sorted by expert (ops/fused.py ``moe_dropless``).  The two layers
share no code yet (ROADMAP Design: two MoE layers).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...distributed.meta_parallel import annotate
from ..layer_base import Layer
from .. import initializer as I
from ...ops import fused
from ...tensor import apply
from .common import Linear

__all__ = ["MoELayer", "DroplessMoE"]

EP_AXIS = "ep"


class MoELayer(Layer):
    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, activation="gelu", ep_axis=EP_AXIS):
        super().__init__()
        if top_k not in (1, 2):
            raise ValueError("top_k must be 1 or 2 (Switch / GShard routing)")
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.ep_axis = ep_axis
        self.gate = Linear(d_model, num_experts, bias_attr=False)
        init = I.XavierUniform()
        self.w1 = annotate(self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=init),
            ep_axis, None, None)
        self.b1 = annotate(self.create_parameter(
            [num_experts, d_hidden], is_bias=True), ep_axis, None)
        self.w2 = annotate(self.create_parameter(
            [num_experts, d_hidden, d_model], default_initializer=init),
            ep_axis, None, None)
        self.b2 = annotate(self.create_parameter(
            [num_experts, d_model], is_bias=True), ep_axis, None)
        self.l_aux = None  # load-balance aux loss of the last forward

    def forward(self, x):
        gate_logits = self.gate(x)
        E, K = self.num_experts, self.top_k
        act = jax.nn.gelu if self.activation == "gelu" else jax.nn.relu
        cf = self.capacity_factor

        def f(xv, gl, w1, b1, w2, b2):
            B, S, D = xv.shape
            N = B * S
            xt = xv.reshape(N, D)
            probs = jax.nn.softmax(gl.reshape(N, E).astype(jnp.float32), -1)
            cap = int(max(1, round(cf * N * K / E)))

            # --- route (top-1, then optional second choice) ---------------
            idx1 = jnp.argmax(probs, -1)
            mask1 = jax.nn.one_hot(idx1, E, dtype=jnp.float32)       # [N, E]
            pos1 = jnp.cumsum(mask1, axis=0) * mask1                 # 1-based
            keep1 = (pos1 <= cap) * mask1
            routes = [(keep1, pos1)]
            if K == 2:
                p2 = probs * (1.0 - mask1)
                idx2 = jnp.argmax(p2, -1)
                mask2 = jax.nn.one_hot(idx2, E, dtype=jnp.float32)
                pos2 = (jnp.cumsum(mask2, axis=0) +
                        keep1.sum(0, keepdims=True)) * mask2
                routes.append(((pos2 <= cap) * mask2, pos2))

            # --- dispatch/combine one-hot tensors [N, E, cap] -------------
            def slots(keep, pos):
                s = ((pos - 1.0) * keep).sum(-1).astype(jnp.int32)
                oh = jax.nn.one_hot(s, cap, dtype=jnp.float32)       # [N, cap]
                return keep[:, :, None] * oh[:, None, :]

            dispatch = sum(slots(k_, p_) for k_, p_ in routes)       # [N,E,cap]
            gates = probs[:, :, None] * dispatch                     # weights
            buf = jnp.einsum("nec,nd->ecd", dispatch, xt.astype(jnp.float32))

            # --- expert FFN, batched over E (ep-sharded under jit) --------
            h = act(jnp.einsum("ecd,edh->ech", buf.astype(xv.dtype), w1)
                    + b1[:, None])
            out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None]
            y = jnp.einsum("nec,ecd->nd", gates, out.astype(jnp.float32))

            # GShard load-balance aux: E * sum_e mean(prob_e) * frac_routed_e
            l_aux = (probs.mean(0) * mask1.mean(0)).sum() * E
            return y.reshape(B, S, D).astype(xv.dtype), l_aux

        out, aux = apply(f, x, gate_logits, self.w1, self.b1, self.w2,
                         self.b2, _multi_out=True)
        self.l_aux = aux
        return out


class DroplessMoE(Layer):
    """Gated-SiLU experts behind a softmax router, top-k of E with no
    capacity: every routed token is computed.

        p = softmax(W_r x) in float32; the k largest; w_e = p_e / sum of
        the k (``norm_topk_prob``); y = sum_e w_e W_down,e (silu(W_gate,e x)
        * W_up,e x)

    x [..., d_model] -> [..., d_model].  ``count`` (bool, x's leading
    shape) marks the rows that matter (a serving step's live lanes) and
    asks for their routing's statistics too: returns (y, assignments [E]
    int32 of the counted rows, experts they touched as an int32 scalar).
    A row it does not mark is padding: it goes through the first counted
    row's experts, so it fetches no expert's weights of its own (a dead
    lane's stale tokens would otherwise touch experts nobody asked for),
    and its output means nothing."""

    def __init__(self, d_model, d_hidden, num_experts, top_k,
                 norm_topk_prob=True, weight_attr=None):
        super().__init__()
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.norm_topk_prob = norm_topk_prob
        self.router = Linear(d_model, num_experts, weight_attr=weight_attr,
                             bias_attr=False)
        self.w_gate = self.create_parameter(
            [num_experts, d_model, d_hidden], attr=weight_attr)
        self.w_up = self.create_parameter(
            [num_experts, d_model, d_hidden], attr=weight_attr)
        self.w_down = self.create_parameter(
            [num_experts, d_hidden, d_model], attr=weight_attr)

    def forward(self, x, count=None):
        E, K, norm = self.num_experts, self.top_k, self.norm_topk_prob
        train = self.training

        def f(xv, rw, wg, wu, wd, *cnt):
            xt = xv.reshape(-1, xv.shape[-1])
            p = jax.nn.softmax(jnp.dot(
                xt, rw, preferred_element_type=jnp.float32), -1)
            top, idx = jax.lax.top_k(p, K)
            if norm:
                top = top / top.sum(-1, keepdims=True)
            if cnt:
                live = cnt[0].reshape(-1, 1)
                idx = jnp.where(live, idx, idx[jnp.argmax(live[:, 0])])
            y = fused.moe_dropless(xt, idx, top, wg, wu, wd,
                                   differentiable=train).reshape(xv.shape)
            if not cnt:
                return y
            per = jnp.zeros((E,), jnp.int32).at[idx].add(
                jnp.broadcast_to(live.astype(jnp.int32), idx.shape))
            return y, per, (per > 0).sum(dtype=jnp.int32)

        args = (x, self.router.weight, self.w_gate, self.w_up, self.w_down)
        if count is None:
            return apply(f, *args)
        return apply(f, *args, count, _multi_out=True)
