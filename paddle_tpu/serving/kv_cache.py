"""Device-resident PAGED KV cache for continuous-batching decode.

The generation engine's whole mutable decode state is ONE pytree of
fixed-shape jax arrays: a page pool ``[layers, num_pages, page_size,
nkv, hd]`` of the model's KV heads (the fused_multi_transformer CacheKV
layout broken into
fixed-size pages, vLLM-style), an int32 per-slot page table
``[max_slots, pages_per_slot]`` (-1 = unmapped), a free-list register
(``free_stack`` + scalar ``free_count``), and the per-slot lane
registers (pending token, write position, active mask, sampling params,
per-slot PRNG keys, pinned shared-page count).

Every jitted transition (insert / decode / release / reclaim) takes the
state as its first state-argument with ``donate_argnums`` — the
TrainEngine donation contract from hapi/engine.py — so XLA rewrites the
pool in place and the KV bytes NEVER round-trip to host.  Donation alone
does not make it so: the program must also keep the pool whole.  The
decode and verify steps hand the model a ``PagedKV`` source (below): each
attention layer calls its ``attend``, which scatters that layer's rows
with ``pool.at[layer, page, off].set`` and hands the paged kernel the
whole pool with a static ``layer``: a per-layer ``pool[i]`` handed to the
kernel, or a ``jnp.stack`` of planes at the end, compiles to a copy of
every plane and of both pools each step, whatever is donated (PERF.md,
PR 25).  ``GenerationEngine.start()`` logs the decode executable's
temporaries beside the cache size.  Page
allocation happens IN-GRAPH: admission maps ``ceil(len/page_size)``
pages off the free stack, decode pops a fresh tail page the iteration a
lane's write position crosses a page boundary, and retirement pushes a
lane's private pages back — so cache HBM is set by actual token
footprint (``num_pages``), not ``max_slots * S_max`` worst case.

Pages with table index below a lane's ``pinned`` register are SHARED
(prefix-cache pages, serving/prefix_cache.py): the device never frees
them; the host returns them through ``reclaim_pages`` once their
refcount drops to zero.  The free-list discipline assumes the host
admits only requests whose worst-case page demand is reserved
(serving/scheduler.py) — ``take_pages`` underflows silently otherwise.

A model whose ``cfg`` declares ``state_layers`` (layers that hold a
recurrent state and no page: a state-space mixer) gets a second kind of
per-slot state in the same pytree: ``ssm`` and ``conv``, every slot's
state and convolution tail a state layer, and beside them a pool of
snapshots (``snap_ssm``, ``snap_conv``).  The pool of pages then holds the
OTHER layers' planes alone.  A slot's state is zero at a cold admission,
restored from a snapshot at a prefix hit (a page id maps K/V back, nothing
maps a state back: a hit is worth only as deep as a snapshot lies), both
inside the admission's own executable; the decode step rewrites the live
lanes' states in place (donated and aliased through the kernel: no second
copy) and never reads a dead lane's.  The layers meet it through the state
sources below (``PromptStates``, ``LaneStates``; ``HybridKV`` carries both
kinds), as attention meets its keys through ``PagedKV`` and ``PrefixKV``.

This module is layout + traced transitions only; scheduling policy lives
in serving/scheduler.py and the compiled-executable lifecycle in
serving/generation.py.  The pool's layout is indexed here and in
ops/pallas/paged_attention.py and nowhere else: a model is handed a KV
source (``PagedKV``, ``PrefixKV``) and calls ``attend(layer, q, k, v)``.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import fused
from ..tensor import unwrap

__all__ = ["CacheGeometry", "PagedKV", "PrefixKV", "HybridKV", "LaneStates",
           "PromptStates", "make_state", "state_specs", "take_pages",
           "push_pages", "write_prompt", "admit_slot", "release_slots",
           "slide_window", "reclaim_pages", "initial_states", "put_states"]


@dataclass(frozen=True)
class CacheGeometry:
    """Static shape of the decode state — one geometry == one decode
    executable (the zero-steady-state-compile invariant).

    ``num_pages`` bounds cache HBM: 0 (the default) sizes the pool
    dense-equivalently at ``max_slots * pages_per_slot`` so every slot
    can always hold S_max tokens; smaller pools oversubscribe slots
    against actual footprint (the scheduler queues admissions that
    cannot reserve their worst case)."""
    num_layers: int
    max_slots: int
    max_seq_len: int       # S_max: prompt + generated tokens per slot
    num_heads: int         # QUERY heads; the pool holds num_kv_heads
    head_dim: int
    vocab_size: int
    page_size: int = 16
    num_pages: int = 0     # 0 = max_slots * pages_per_slot
    dtype: str = "float32"
    # speculative decode: the draft model's KV lives in a parallel pool
    # indirected through the SAME page table (one allocation decision
    # covers both models); 0 layers = no draft pool in the state
    draft_layers: int = 0
    draft_num_heads: int = 0
    draft_head_dim: int = 0
    # grouped heads: the KV heads the pool holds, each read by
    # num_heads / num_kv_heads query heads; 0 = num_heads (one each)
    num_kv_heads: int = 0
    # generation by blocks (a model whose cfg gives block_length): the
    # lanes then carry a block's registers; 0 = one token a lane a step
    block_length: int = 0
    # routed experts a layer (0 = none): the state then counts the
    # assignments of the live lanes' rows, layer by expert
    num_experts: int = 0
    # layers that see a window of keys: one entry a layer, the window in
    # tokens (0 = the layer sees everything); () = every layer sees
    # everything, and the state is the one pool and one table it always
    # was.  With windows the window layers get planes, a page table and a
    # free list of their own (``window_pages`` pages, derived below): a
    # lane maps there only the pages that meet its window
    windows: tuple = ()
    # layers that hold no page but a recurrent state (a state-space mixer):
    # their indices, and one lane's state (float32) and convolution tail in
    # one such layer.  () = every layer holds pages, and the state is what
    # it always was.  With state layers the pool has a plane for each OTHER
    # layer only, every slot a state and a tail for each of these, and a
    # pool of ``state_snapshots`` snapshots stands beside them, from which
    # a prefix hit restores (a page id maps K/V back; nothing maps a state
    # back)
    state_layers: tuple = ()
    state_shape: tuple = ()
    conv_shape: tuple = ()
    # the prompt pass scans in chunks of ``state_chunk`` tokens, and a state
    # is held ``state_pack`` heads to a row (``fused.ssm_pack_state``)
    state_chunk: int = 0
    state_pack: int = 1

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        object.__setattr__(self, "state_layers",
                           tuple(int(i) for i in self.state_layers))
        if self.state_layers and (
                any(self.windows) or self.draft_layers or self.block_length
                or not self.state_shape or not self.conv_shape
                or self.state_chunk < 1
                or len(set(self.state_layers)) == self.num_layers):
            raise ValueError(
                "layers with a recurrent state are held beside at least one "
                "layer with pages, with their state's and tail's shapes and "
                "the scan's chunk given, and without window layers, a draft model or "
                "generation by blocks (none of those paths is written)")
        object.__setattr__(self, "windows",
                           tuple(int(w) for w in self.windows))
        if not any(self.windows):
            object.__setattr__(self, "windows", ())
        elif (len(self.windows) != self.num_layers
              or len(set(self.windows) - {0}) != 1
              or self.window % self.page_size
              or self.draft_layers or self.block_length):
            raise ValueError(
                f"windows {self.windows}: one entry a layer ("
                f"{self.num_layers}), one window size, a multiple of the "
                f"page size {self.page_size}, and neither a draft model "
                "nor generation by blocks (none of those paths is written)")
        if self.num_kv_heads == 0:
            object.__setattr__(self, "num_kv_heads", self.num_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads do not divide over "
                f"{self.num_kv_heads} KV heads")
        if self.block_length and (self.page_size % self.block_length
                                  or self.max_seq_len % self.block_length):
            raise ValueError(
                f"page_size {self.page_size} and max_seq_len "
                f"{self.max_seq_len} must be multiples of the block length "
                f"{self.block_length}: a block lies in one page, and a "
                "shared prefix page is a whole number of blocks")
        if self.num_pages == 0:
            object.__setattr__(self, "num_pages",
                               self.max_slots * self.pages_per_slot)
        if self.num_pages < 1:
            raise ValueError(
                f"num_pages must be >= 1, got {self.num_pages}")

    @property
    def pages_per_slot(self) -> int:
        return -(-self.max_seq_len // self.page_size)

    @property
    def window(self) -> int:
        """The window layers' window in tokens (0 = no such layer)."""
        return max(self.windows, default=0)

    @property
    def full_layers(self) -> tuple:
        """The model's layers whose K/V the full pool holds, in order."""
        held = set(self.state_layers)
        return tuple(i for i in range(self.num_layers)
                     if not (self.windows and self.windows[i])
                     and i not in held)

    @property
    def state_snapshots(self) -> int:
        """Snapshots of a lane's whole recurrent state the engine can keep
        for prefix hits to restore from: one for every six slots (0 without
        state layers).  The pool's one sizing rule: where it is short the
        oldest snapshot goes, and a later hit on its prefix scans from a
        shallower one or from zero."""
        return max(1, self.max_slots // 6) if self.state_layers else 0

    def state_bytes(self) -> int:
        """Bytes of ONE lane's recurrent state over all state layers (a
        snapshot costs the same): float32 states, tails in ``dtype``."""
        import numpy as np

        return len(self.state_layers) * (
            4 * int(np.prod(self.state_shape))
            + np.dtype(self.dtype).itemsize * int(np.prod(self.conv_shape)))

    @property
    def window_layers(self) -> tuple:
        return tuple(i for i, w in enumerate(self.windows) if w)

    @property
    def window_cols(self) -> int:
        """Private pages a lane can hold in the window pool at once: the
        pages a window can meet, and the tail page a step maps before it
        lets the last one go."""
        return self.window // self.page_size + 2

    def page_walk(self, positions) -> tuple[int, int]:
        """What one decode (or block) step's paged attention has to walk,
        summed over the layers: (the page slots of every lane's every
        table column, which a grid over the table visits whatever is
        mapped; the pages that the extents of lanes attending from
        ``positions`` cover, None for a lane that has ended: a full
        layer's from column 0, a window layer's from the first column
        that meets the window, to the position's)."""
        ps, cols = self.page_size, self.pages_per_slot
        n_win = len(self.window_layers)
        n_full = len(self.full_layers)
        win_cols = min(cols, (self.window - 2) // ps + 2) if n_win else 0
        slots = self.max_slots * (n_full * cols + n_win * win_cols)
        live = 0
        for pos in positions:
            if pos is None:         # a lane that has ended on the device
                continue
            last = min(pos // ps, cols - 1)
            first = max(pos - self.window + 1, 0) // ps
            live += n_full * (last + 1) + n_win * max(last - first + 1, 0)
        return slots, live

    @property
    def window_pages(self) -> int:
        """The window pool's capacity (0 without windows): every lane's
        own pages at their most, and a quarter of the full pool's count
        for the prompts a prefix cache keeps whole in both pools, never
        more than the full pool holds (each shared page there has one
        twin here).  The quarter is the pool's one sizing rule: where it
        is short the prefix cache drops its oldest prompts and admissions
        queue, as they do for the full pool; nothing fails."""
        if not self.windows:
            return 0
        return min(self.num_pages,
                   self.max_slots * self.window_cols + self.num_pages // 4)

    def first_col(self, pos: int) -> int:
        """The first table column whose page meets the window of a query
        at ``pos`` (host arithmetic; traced code has ``_first_col``)."""
        return max(int(pos) - self.window + 1, 0) // self.page_size

    @property
    def pool_shape(self):
        return (len(self.full_layers), self.num_pages, self.page_size,
                self.num_kv_heads, self.head_dim)

    @property
    def window_pool_shape(self):
        return (len(self.window_layers), self.window_pages, self.page_size,
                self.num_kv_heads, self.head_dim)

    @property
    def draft_pool_shape(self):
        return (self.draft_layers, self.num_pages, self.page_size,
                self.draft_num_heads, self.draft_head_dim)

    def page_bytes(self) -> int:
        """Bytes ONE page costs across k+v and all layers (draft pool
        included when speculative) — the HBM sizing unit: cache bytes =
        num_pages * page_bytes()."""
        import numpy as np

        per_tok = (len(self.full_layers) * self.num_kv_heads * self.head_dim
                   + self.draft_layers * self.draft_num_heads
                   * self.draft_head_dim)
        return (2 * self.page_size * per_tok
                * np.dtype(self.dtype).itemsize)

    def window_page_bytes(self) -> int:
        """Bytes one page of the window pool costs (k+v, window layers)."""
        import numpy as np

        return (2 * self.page_size * len(self.window_layers)
                * self.num_kv_heads * self.head_dim
                * np.dtype(self.dtype).itemsize)

    def kv_bytes(self) -> int:
        return (self.num_pages * self.page_bytes()
                + self.window_pages * self.window_page_bytes())

    def pages_for(self, n_tokens: int) -> int:
        """Pages an ``n_tokens``-long sequence occupies."""
        return -(-int(n_tokens) // self.page_size)


def make_state(geom: CacheGeometry):
    """Fresh all-pages-free decode state (device arrays).

    Keys: ``kp``/``vp`` the page pools; ``ptab`` the per-slot page
    table (-1 = unmapped); ``free_stack``/``free_count`` the free-list
    register (free page ids live at ``free_stack[:free_count]``, popped
    from the top); per-slot lanes ``tok`` (pending token, written at
    ``pos`` next iteration), ``pos`` (absolute write index), ``active``,
    ``rng`` (per-slot PRNG key), ``pinned`` (table indices below it are
    shared prefix pages the device must not free), and the per-slot
    sampling registers ``do_sample``/``temp``/``top_k``/``eos``/
    ``stop_pos`` (stop_pos = prompt_len + max_new_tokens; a lane retires
    when its next write position would reach it, or on eos).

    A block-generating geometry (``block_length`` B) adds the block's
    registers: ``pos`` is then the block's start, ``blk`` [slots, B] its
    tokens, ``blk_open`` [slots, B] which of them are still masked (a
    position is masked because this register says so, not because its
    token is the mask id), ``blk_step`` [slots, B] the denoising step at
    which each was unmasked (-1 = not yet, or known from the prompt),
    ``step`` the denoising steps the block has had.  With
    ``num_experts`` the state counts routed assignments of live lanes'
    rows: ``moe_counts`` [layers, experts] and ``moe_touched`` [layers]
    (experts with at least one, summed over steps).  With ``windows`` the
    window layers' pool: ``wkp``/``wvp``, ``wtab``, ``wfree_stack``/
    ``wfree_count`` (``kp``/``vp`` then hold the other layers alone) and
    ``w_released``, the pages let go behind the window since start.
    With ``state_layers``: ``ssm`` [state layers, slots, *state_shape]
    float32 and ``conv`` [state layers, slots, prod(conv_shape)], every slot's
    recurrent state and convolution tail (``kp``/``vp`` then hold the other
    layers alone), and ``snap_ssm``/``snap_conv``, the same for
    ``state_snapshots`` snapshots.  A slot's state is written whole at
    every admission (zero, restored or scanned), updated in place by the
    decode step for the live lanes, and never read for a dead one: release
    has nothing to give back.
    """
    S = geom.max_slots
    key_shape = jax.random.PRNGKey(0).shape  # (2,) for threefry
    state = {
        "kp": jnp.zeros(geom.pool_shape, jnp.dtype(geom.dtype)),
        "vp": jnp.zeros(geom.pool_shape, jnp.dtype(geom.dtype)),
        "ptab": jnp.full((S, geom.pages_per_slot), -1, jnp.int32),
        "free_stack": jnp.arange(geom.num_pages, dtype=jnp.int32),
        "free_count": jnp.int32(geom.num_pages),
        "pinned": jnp.zeros((S,), jnp.int32),
        "tok": jnp.zeros((S,), jnp.int32),
        "pos": jnp.zeros((S,), jnp.int32),
        "active": jnp.zeros((S,), bool),
        "rng": jnp.zeros((S,) + tuple(key_shape), jnp.uint32),
        "do_sample": jnp.zeros((S,), bool),
        "temp": jnp.ones((S,), jnp.float32),
        "top_k": jnp.zeros((S,), jnp.int32),
        "eos": jnp.full((S,), geom.vocab_size, jnp.int32),  # V = never
        "stop_pos": jnp.zeros((S,), jnp.int32),
    }
    if geom.block_length:
        state["blk"] = jnp.zeros((S, geom.block_length), jnp.int32)
        state["blk_open"] = jnp.zeros((S, geom.block_length), bool)
        state["blk_step"] = jnp.full((S, geom.block_length), -1, jnp.int32)
        state["step"] = jnp.zeros((S,), jnp.int32)
    if geom.num_experts:
        state["moe_counts"] = jnp.zeros(
            (geom.num_layers, geom.num_experts), jnp.int32)
        state["moe_touched"] = jnp.zeros((geom.num_layers,), jnp.int32)
    if geom.windows:
        # the window layers' planes, table and free list, and the pages
        # the steps have let go behind the window since start
        state["wkp"] = jnp.zeros(geom.window_pool_shape,
                                 jnp.dtype(geom.dtype))
        state["wvp"] = jnp.zeros(geom.window_pool_shape,
                                 jnp.dtype(geom.dtype))
        state["wtab"] = jnp.full((S, geom.pages_per_slot), -1, jnp.int32)
        state["wfree_stack"] = jnp.arange(geom.window_pages,
                                          dtype=jnp.int32)
        state["wfree_count"] = jnp.int32(geom.window_pages)
        state["w_released"] = jnp.int32(0)
    if geom.state_layers:
        # every slot's recurrent state and convolution tail, layer by
        # layer, and the pool of snapshots beside them
        n, snaps = len(geom.state_layers), geom.state_snapshots
        dt = jnp.dtype(geom.dtype)
        state["ssm"] = jnp.zeros((n, S) + geom.state_shape, jnp.float32)
        # a tail is held flat, [d_conv - 1, conv_dim] as one row: three rows
        # of a [3, 4352] tile are padded to sixteen, and the compiler then
        # keeps the array "compressed" and copies all of it around every
        # layer's use (1.75 s of a 12 s window: PERF.md section 6, PR 41)
        tail = (int(np.prod(geom.conv_shape)),)
        state["conv"] = jnp.zeros((n, S) + tail, dt)
        state["snap_ssm"] = jnp.zeros((n, snaps) + geom.state_shape,
                                      jnp.float32)
        state["snap_conv"] = jnp.zeros((n, snaps) + tail, dt)
    if geom.draft_layers:
        # draft-model KV pool, same page ids as kp/vp: one page-table
        # row addresses both models' cache for a lane
        state["dkp"] = jnp.zeros(geom.draft_pool_shape,
                                 jnp.dtype(geom.dtype))
        state["dvp"] = jnp.zeros(geom.draft_pool_shape,
                                 jnp.dtype(geom.dtype))
    return state


def state_specs(state, shardings=None):
    """ShapeDtypeStructs mirroring a state pytree (AOT lowering input).
    ``shardings``: optional matching pytree of NamedShardings — attached
    so the layout-aware engine lowers its executables with the page
    pool's head axis pinned over tp."""
    if shardings is None:
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, shardings)


# -- KV sources: what a model's attention layers attend over ----------------
# One protocol, ``attend(layer, q, k, v, head_axis=None) -> (ctx, source')``:
# q [B, C, nh, hd] and k/v [B, C, nkv, hd] are the new tokens' projections
# (raw jax arrays; nkv divides nh, query head h reads KV head h // (nh /
# nkv)), ``layer`` a static int, ``head_axis`` the mesh axis the model pins
# its heads to (or None); ctx [B, C, nh, hd] is the attention output and
# source' the source with the new rows taken in.  Pytrees, so a source
# passes through ``jit`` and ``functional_call`` like the arrays it holds.

def _first_col(pos, window, page_size):
    """The first table column whose page meets ``[pos - window + 1, pos]``
    (traced ``pos``)."""
    return jnp.maximum(pos - window + 1, 0) // page_size


def _plane(windows, layer):
    """(window, plane) of model layer ``layer``: the window it sees (0 =
    everything) and its plane among its pool's layers (its own index
    where no layer has a window)."""
    if not windows:
        return 0, layer
    w = windows[layer]
    return w, sum(1 for x in windows[:layer] if bool(x) == bool(w))


def _attend(q, keys, values, valid):
    """``fused.masked_attention`` for nh query heads over nkv KV heads: the
    nh / nkv query heads of a KV head are folded into the query axis, so
    the keys and values are read once, as cached, and never expanded."""
    B, Q, nh, hd = q.shape
    nkv = keys.shape[2]
    if nkv == nh:
        return fused.masked_attention(q, keys, values, valid)
    g = nh // nkv
    qg = q.reshape(B, Q, nkv, g, hd).transpose(0, 1, 3, 2, 4) \
        .reshape(B, Q * g, nkv, hd)
    vg = valid if valid.shape[1] == 1 else jnp.repeat(valid, g, axis=1)
    ctx = fused.masked_attention(qg, keys, values, vg)
    return ctx.reshape(B, Q, g, nkv, hd).transpose(0, 1, 3, 2, 4) \
        .reshape(B, Q, nh, hd)


@jax.tree_util.register_dataclass
@dataclass
class PagedKV:
    """The page pools as one step of the engine sees them: decode (one
    token a lane, ``positions`` [slots]) and speculative verification (a
    chunk of C candidates a lane at consecutive positions, ``positions``
    [slots, C]).

    k_pages/v_pages: [layers, num_pages, page_size, nkv, hd], the WHOLE
    pools: ``attend`` writes and reads plane ``layer`` and returns the
    whole pools, so a donated pool is rewritten in place and no plane is
    ever sliced out or stacked back.  rows: [slots, pages_per_slot] int32
    page table (-1 = unmapped); active: [slots] bool, inactive lanes
    write nowhere; seq_cap: STATIC attention extent (the engine's S_max):
    the gathered view is sliced to it so the softmax reduction shape
    matches ``generate``'s dense cache exactly, which keeps an engine
    lane bitwise-equal to a solo run.  Unmapped (-1) table entries gather
    an arbitrary resident page whose positions sit past the validity
    mask, so they contribute exactly 0 to the softmax.

    Causality inside a chunk falls out of the position mask: candidate
    i's query admits exactly the keys at slots <= positions[b, i], the
    committed history plus candidates 0..i: the reduction extent the
    one-token step would have seen, so accepted tokens stay bitwise-equal
    to the sequential path.

    ``limits``, when given, is the last key position a query may see in
    place of its own: a block-generating step gives every query of a block
    the block's end, so the block is visible both ways over the committed
    prefix.  [slots]: ONE a lane, what ``block_step`` hands; [slots, C]:
    one a query.

    Which steps read the pool through the paged kernels
    (ops/pallas/paged_attention.py: a lane's mapped pages walked in place)
    and which gather the table's pages into a dense view follows from
    these operands alone.  The walk takes ONE last key a lane: a
    one-token step (``positions`` [slots], full and window layers), and a
    chunk whose ``limits`` is [slots] on a layer without a window (the
    block step: the C queries of a lane are rows of the same products).
    The gather takes a last key a query (``positions`` [slots, C] with no
    ``limits``, speculative verification; ``limits`` [slots, C]), a chunk
    on a window layer, and whatever the kernel refuses (counted).
    """
    k_pages: Any
    v_pages: Any
    rows: Any
    positions: Any
    active: Any
    seq_cap: int = field(metadata=dict(static=True))
    limits: Any = None
    # layers that see a window (CacheGeometry.windows; () = none): their
    # K/V live in wk_pages/wv_pages [window layers, window_pages, ...],
    # mapped by wrows, and k_pages/v_pages hold the other layers alone
    wk_pages: Any = None
    wv_pages: Any = None
    wrows: Any = None
    windows: tuple = field(default=(), metadata=dict(static=True))

    def attend(self, layer, q, k, v, head_axis=None):
        w, layer = _plane(self.windows, layer)
        if w:
            ctx, kp, vp = self._attend(layer, q, k, v, head_axis,
                                       self.wk_pages, self.wv_pages,
                                       self.wrows, w)
            return ctx, replace(self, wk_pages=kp, wv_pages=vp)
        ctx, kp, vp = self._attend(layer, q, k, v, head_axis, self.k_pages,
                                   self.v_pages, self.rows, 0)
        return ctx, replace(self, k_pages=kp, v_pages=vp)

    def _attend(self, layer, q, k, v, head_axis, kp, vp, rows, window):
        pos = self.positions
        B, _, nh, hd = q.shape
        num_pages, ps, nkv = kp.shape[1], kp.shape[2], kp.shape[3]
        lane, active = jnp.arange(B), self.active
        one = pos.ndim == 1
        if one:
            k, v = k[:, 0], v[:, 0]                  # [slots, nkv, hd]
        else:
            lane, active = lane[:, None], active[:, None]
        # token (b, i) writes its K/V at (layer, rows[b, pos // ps],
        # pos % ps); inactive lanes target one-past-the-pool and are
        # dropped.  Clamped duplicate positions of a chunk (end of budget)
        # may collide: whichever write wins is garbage no emitted query's
        # mask ever exposes.
        page = rows[lane, jnp.clip(pos // ps, 0, rows.shape[1] - 1)]
        page = jnp.where(active, page, num_pages)
        off = pos % ps
        kp = kp.at[layer, page, off].set(k.astype(kp.dtype), mode="drop")
        vp = vp.at[layer, page, off].set(v.astype(vp.dtype), mode="drop")
        # hot path: the Pallas ragged kernel walks each lane's page-table
        # row and reads plane `layer` of the pool in place.  It takes one
        # last key a lane: a one-token step's position, or the `limits`
        # [slots] that a block step gives all of a lane's queries (no
        # window there: a window is measured from each query's own
        # position).  None => flag off / untileable geometry (counted in
        # paddle_pallas_fallbacks_total).  The dense gather below is the
        # reference, the fallback, and the path of a chunk with a last key
        # a QUERY (speculative verification, causal inside the chunk: one
        # step per K drafted tokens) or on a window layer, by design and
        # uncounted.  A pool of KV heads, each read by a group of query
        # heads, a layer with a window and a lane of several queries have
        # a kernel of their own behind the same call.
        limits = self.limits
        ctx = None
        if one:
            ctx = fused.paged_decode_attention(
                q, kp, vp, rows, pos, self.seq_cap, layer,
                tp_axis=head_axis, window=window)
        elif limits is not None and limits.ndim == 1 and not window:
            ctx = fused.paged_decode_attention(
                q, kp, vp, rows, limits, self.seq_cap, layer,
                tp_axis=head_axis)
        # the last key each query sees, [slots, C] or [slots, 1]
        last = (pos if limits is None else limits).reshape(B, -1)
        if ctx is None and window:
            # the gather is as wide as the pages a window can meet (and a
            # chunk can span), not the table
            qpos = pos.reshape(B, -1)
            ncol = min(rows.shape[1],
                       (window - 2) // ps + 2 + (qpos.shape[1] - 1) // ps + 1)
            col = _first_col(qpos.min(axis=1), window, ps)[:, None] \
                + jnp.arange(ncol)[None]
            ids = jnp.where(col < rows.shape[1], jnp.take_along_axis(
                rows, jnp.clip(col, 0, rows.shape[1] - 1), axis=1), -1)
            gidx = jnp.clip(ids, 0, num_pages - 1)
            kg = kp[layer, gidx].reshape(B, ncol * ps, nkv, hd)
            vg = vp[layer, gidx].reshape(B, ncol * ps, nkv, hd)
            kpos = (col[:, :, None] * ps + jnp.arange(ps)).reshape(B, 1, -1)
            valid = (kpos <= last[:, :, None]) \
                & (kpos > qpos[:, :, None] - window) \
                & jnp.repeat(ids >= 0, ps, axis=1)[:, None]
            ctx = _attend(q, kg, vg, valid)
        elif ctx is None:
            gidx = jnp.clip(rows, 0, num_pages - 1)
            kg = kp[layer, gidx].reshape(B, rows.shape[1] * ps, nkv, hd)
            vg = vp[layer, gidx].reshape(B, rows.shape[1] * ps, nkv, hd)
            valid = jnp.arange(self.seq_cap)[None, None, :] \
                <= last[:, :, None]
            ctx = _attend(
                q, kg[:, :self.seq_cap], vg[:, :self.seq_cap], valid)
        return unwrap(ctx), kp, vp


@jax.tree_util.register_dataclass
@dataclass
class PrefixKV:
    """A prompt's already-resident prefix, for a suffix-only prefill (a
    prefix-cache hit, or one chunk of a chunked prefill): queries are the
    suffix tokens (absolute positions ``prefix_len + i``), keys are
    [prefix ++ suffix] with the prefix entries valid below ``prefix_len``
    and the suffix causal, so the shared pages are never recomputed.
    ``block`` (static) > 1 makes the suffix's mask the block mask: suffix
    token i sees suffix token j where j // block <= i // block (the prefix
    ends at a page boundary, a whole number of blocks).

    prefix_k/prefix_v: [layers, C, nkv, hd] gathered from the pool (C
    static, entries >= prefix_len garbage the mask hides); prefix_len:
    traced scalar; suffix: the (k, v) [Ss, nkv, hd] each layer attended
    so far, which ``write_prompt`` pages in at the (page-aligned) prefix
    boundary.  Token- (not bitwise-) equivalent to a full prefill: the
    math matches up to float reassociation of the explicit softmax
    against the fused causal kernel.
    """
    prefix_k: Any
    prefix_v: Any
    prefix_len: Any
    suffix: tuple = ()
    block: int = field(default=1, metadata=dict(static=True))
    # layers that see a window (CacheGeometry.windows; () = none).  The
    # prefix is then held RIGHT-aligned, its last token in the last row:
    # prefix_k/prefix_v the other layers' [full layers, C, nkv, hd], and
    # wprefix_k/wprefix_v the window layers' last window of it [window
    # layers, window, nkv, hd], so [prefix ++ suffix] is one run of
    # consecutive positions and every mask is a band of it
    wprefix_k: Any = None
    wprefix_v: Any = None
    windows: tuple = field(default=(), metadata=dict(static=True))

    @classmethod
    def gather(cls, k_pages, v_pages, page_ids, prefix_len, block=1):
        """The prefix held by pool pages ``page_ids`` [n] (-1 entries
        gather an arbitrary page past ``prefix_len``)."""
        L, num_pages, ps, nkv, hd = k_pages.shape
        gidx = jnp.clip(page_ids, 0, num_pages - 1)
        return cls(k_pages[:, gidx].reshape(L, gidx.shape[0] * ps, nkv, hd),
                   v_pages[:, gidx].reshape(L, gidx.shape[0] * ps, nkv, hd),
                   jnp.asarray(prefix_len, jnp.int32), block=block)

    @classmethod
    def gather_windowed(cls, state, page_ids, wpage_ids, n_pages, cols,
                        windows):
        """The first ``n_pages`` pages of a prompt, from both pools of a
        window engine's ``state``: table columns ``[n_pages - cols,
        n_pages)`` of ``page_ids`` for the layers that see everything, and
        the last window's worth of ``wpage_ids`` for the others (columns
        before 0 and unmapped ones gather an arbitrary page the masks
        hide).  ``cols`` static, ``n_pages`` traced."""
        ps = state["kp"].shape[2]
        window = max(windows)

        def right(kp, vp, ids, n):
            col = n_pages - n + jnp.arange(n)
            gidx = jnp.clip(ids[jnp.clip(col, 0, ids.shape[0] - 1)], 0,
                            kp.shape[1] - 1)
            shape = (kp.shape[0], n * ps) + kp.shape[3:]
            return kp[:, gidx].reshape(shape), vp[:, gidx].reshape(shape)

        pk, pv = right(state["kp"], state["vp"], page_ids, cols)
        wk, wv = right(state["wkp"], state["wvp"], wpage_ids, window // ps)
        return cls(pk, pv, jnp.asarray(n_pages * ps, jnp.int32),
                   wprefix_k=wk, wprefix_v=wv, windows=tuple(windows))

    def attend(self, layer, q, k, v, head_axis=None):
        if self.windows:
            w, plane = _plane(self.windows, layer)
            pk, pv = (self.wprefix_k, self.wprefix_v) if w else \
                (self.prefix_k, self.prefix_v)
            C = pk.shape[1]
            # key c of [prefix ++ suffix] is at position prefix_len - C + c
            # and suffix token i at prefix_len + i: i sees c <= i + C, at
            # most a window back, and nothing before position 0
            ctx = fused.banded_attention(
                q, jnp.concatenate([pk[plane][None].astype(k.dtype), k], 1),
                jnp.concatenate([pv[plane][None].astype(v.dtype), v], 1),
                offset=C, window=w, floor=C - self.prefix_len)
            return ctx, replace(self, suffix=self.suffix + ((k[0], v[0]),))
        S = q.shape[1]
        pk, pv = self.prefix_k[layer][None], self.prefix_v[layer][None]
        C = pk.shape[1]
        i = jnp.arange(S)[:, None]
        j = jnp.arange(C + S)[None, :]
        if self.block > 1:       # a query sees to the end of its block
            i = i // self.block * self.block + self.block - 1
        ok = (j < self.prefix_len) | ((j >= C) & (j - C <= i))
        ctx = _attend(
            q, jnp.concatenate([pk.astype(k.dtype), k], axis=1),
            jnp.concatenate([pv.astype(v.dtype), v], axis=1), ok[None])
        return ctx, replace(self, suffix=self.suffix + ((k[0], v[0]),))

    def suffix_kv(self):
        """(k, v) [layers, Ss, nkv, hd] of the suffix, for ``write_prompt``."""
        return (jnp.stack([k for k, _ in self.suffix]),
                jnp.stack([v for _, v in self.suffix]))


# -- state sources: what a model's recurrent layers run over ----------------
# Two calls a layer, in this order: ``window(plane, xBC) -> (past, source')``
# lays the new tokens' convolution inputs xBC [B, S, C] behind the d_conv - 1
# that came before them ([B, S + d_conv - 1, C]) and takes the new tail in;
# ``scan(plane, x, dt, A, B, C) -> (y, source')`` runs H_t = exp(dt_t A)
# H_{t-1} + dt_t x_t B_t^T, y_t = H_t C_t over x [B, S, H, P], dt [B, S, H]
# (after the softplus), B and C [B, S, N], and takes the new state in.
# ``plane`` is the layer's number among the state layers.  The states are
# held in ``fused.ssm_pack_state``'s layout.

@jax.tree_util.register_dataclass
@dataclass
class PromptStates:
    """ONE request's prompt pass (cold, behind a prefix hit, or one chunk of
    a chunked prefill): the scan starts from ``ssm0``/``conv0`` [state
    layers, ...] (zero, a restored snapshot, or the slot's own state so
    far; a tail is held flat, as the decode state holds it), runs over the first ``length`` tokens of the bucket (a padded
    position leaves state and tail untouched) in chunks of ``chunk``, and
    keeps of every layer the state and tail after the last token and after
    token ``snap_at`` - 1 (a shared page boundary the pass crosses)."""
    ssm0: Any
    conv0: Any
    length: Any
    snap_at: Any
    chunk: int = field(metadata=dict(static=True))
    pack: int = field(metadata=dict(static=True))
    tails: tuple = ()
    states: tuple = ()

    @classmethod
    def zeros(cls, cfg, length, snap_at=0):
        """A pass from the zero state for a model's ``cfg`` (state_layers,
        state_shape, conv_shape, state_pack, state_chunk)."""
        n = len(cfg.state_layers)
        return cls(jnp.zeros((n,) + tuple(cfg.state_shape), jnp.float32),
                   jnp.zeros((n, int(np.prod(cfg.conv_shape))), jnp.float32),
                   jnp.asarray(unwrap(length), jnp.int32),
                   jnp.asarray(unwrap(snap_at), jnp.int32),
                   chunk=int(cfg.state_chunk), pack=int(cfg.state_pack))

    def window(self, plane, xBC):
        tail = self.conv0[plane].reshape(-1, xBC.shape[-1])   # held flat
        past = jnp.concatenate([tail[None].astype(xBC.dtype), xBC], axis=1)

        def at(n):      # the tail after token n - 1: inputs [n - k, n)
            return jax.lax.dynamic_slice_in_dim(
                past[0], n, tail.shape[0], 0).reshape(-1)

        return past, replace(self, tails=self.tails + (
            (at(self.length), at(self.snap_at)),))

    def scan(self, plane, x, dt, A, B, C):
        live = jnp.arange(x.shape[1])[:, None] < self.length
        d = jnp.where(live, dt[0], 0.0)
        y, end, starts = fused.ssd_chunk_scan(
            x[0], d, A, B[0], C[0],
            fused.ssm_unpack_state(self.ssm0[plane], self.pack), self.chunk)
        snap = fused.ssd_state_at(x[0], d, A, B[0], starts, self.snap_at,
                                  self.chunk)
        return y[None], replace(self, states=self.states + (
            (fused.ssm_pack_state(end, self.pack),
             fused.ssm_pack_state(snap, self.pack)),))

    def ends(self):
        """(state after the last token [state layers, ...], its tail, the
        state after token ``snap_at`` - 1, its tail), for ``put_states``."""
        return (jnp.stack([e for e, _ in self.states]),
                jnp.stack([t for t, _ in self.tails]),
                jnp.stack([s for _, s in self.states]),
                jnp.stack([t for _, t in self.tails]))


@jax.tree_util.register_dataclass
@dataclass
class LaneStates:
    """The decode step: one token a lane over every slot's state.  ``ssm``
    [state layers, slots, ...] and ``conv`` are the WHOLE arrays of the
    decode state: a layer rewrites its plane in place, the live lanes' part
    of it alone (``live`` [slots] bool; ``lanes`` [slots] lists the live
    lanes first, ``n_live`` how many they are: ``fused.ssm_decode_update``).
    A dead lane's state and tail stay as they are: a slot between two
    chunks of its prompt holds there what its next chunk scans on from."""
    ssm: Any
    conv: Any
    live: Any
    lanes: Any
    n_live: Any

    def window(self, plane, xBC):
        slots, _, width = xBC.shape
        held = self.conv[plane]                               # held flat
        past = jnp.concatenate(
            [held.reshape(slots, -1, width).astype(xBC.dtype), xBC], axis=1)
        new = past[:, 1:].reshape(slots, -1).astype(held.dtype)
        return past, replace(self, conv=self.conv.at[plane].set(
            jnp.where(self.live[:, None], new, held)))

    def scan(self, plane, x, dt, A, B, C):
        y, ssm = fused.ssm_decode_update(
            self.ssm, plane, self.lanes, self.n_live, x[:, 0], dt[:, 0], A,
            B[:, 0], C[:, 0])
        return y[:, None], replace(self, ssm=ssm)


@jax.tree_util.register_dataclass
@dataclass
class HybridKV:
    """Both kinds of state as one source: ``kv`` (``PagedKV`` or
    ``PrefixKV``) for the layers that attend, ``states`` (``LaneStates`` or
    ``PromptStates``) for the layers that scan."""
    kv: Any
    states: Any

    def attend(self, plane, q, k, v, head_axis=None):
        ctx, kv = self.kv.attend(plane, q, k, v, head_axis)
        return ctx, replace(self, kv=kv)

    def window(self, plane, xBC):
        past, states = self.states.window(plane, xBC)
        return past, replace(self, states=states)

    def scan(self, plane, x, dt, A, B, C):
        y, states = self.states.scan(plane, x, dt, A, B, C)
        return y, replace(self, states=states)


# where a prompt pass starts its scan, beside a snapshot's place (>= 0)
SCAN_FROM_ZERO, SCAN_FROM_SLOT = -1, -2


def initial_states(state, slot, start):
    """Where one prompt pass starts its scan, (ssm0, conv0) [state layers,
    ...]: snapshot ``start`` of the pool (>= 0: a prefix hit restores), the
    zero state (``SCAN_FROM_ZERO``: a cold pass), or slot ``slot``'s own
    state so far (``SCAN_FROM_SLOT``: the next chunk of a chunked prefill).
    Traced."""
    start = jnp.asarray(start, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    snap = jnp.clip(start, 0, state["snap_ssm"].shape[1] - 1)

    def pick(snaps, own):
        return jnp.where(start >= 0, snaps[:, snap],
                         jnp.where(start == SCAN_FROM_SLOT, own[:, slot], 0))

    return pick(state["snap_ssm"], state["ssm"]), \
        pick(state["snap_conv"], state["conv"])


def put_states(state, slot, ends, snap_to):
    """Hand a prompt pass's states (``PromptStates.ends``) over: the state
    and tail after its last token to slot ``slot``, those at the shared
    page boundary it crossed to snapshot ``snap_to`` of the pool (-1: it
    leaves none, and the place written is handed what it held).  Each a
    slice updated in place: a scatter that may drop its update makes the
    compiler copy the pool.  ``ends`` () (a model without state layers ran
    the pass): ``state`` as it came."""
    if not ends:
        return state
    ssm_end, conv_end, ssm_snap, conv_snap = ends
    slot = jnp.asarray(slot, jnp.int32)
    keep = jnp.asarray(snap_to, jnp.int32) >= 0
    to = jnp.clip(snap_to, 0, state["snap_ssm"].shape[1] - 1).astype(
        jnp.int32)

    def put(held, at, new, unless=None):
        new = new.astype(held.dtype)[:, None]
        zero = (jnp.int32(0),) * (held.ndim - 2)
        if unless is not None:
            new = jnp.where(unless, new, jax.lax.dynamic_slice(
                held, (jnp.int32(0), at) + zero, new.shape))
        return jax.lax.dynamic_update_slice(held, new,
                                            (jnp.int32(0), at) + zero)

    return dict(
        state,
        ssm=put(state["ssm"], slot, ssm_end),
        conv=put(state["conv"], slot, conv_end),
        snap_ssm=put(state["snap_ssm"], to, ssm_snap, keep),
        snap_conv=put(state["snap_conv"], to, conv_snap, keep))


# -- in-graph free-list register ops ----------------------------------------

def take_pages(free_stack, free_count, need):
    """Pop one page per True lane of ``need`` off the free stack.
    Returns (pages, free_count') — lanes with need=False get -1.  The
    stack array itself is untouched (entries above free_count are
    stale); the host guarantees free_count never underflows by
    reserving worst-case demand at admission."""
    need = need.astype(bool)
    ranks = jnp.cumsum(need.astype(jnp.int32)) - 1
    idx = jnp.clip(free_count - 1 - ranks, 0, free_stack.shape[0] - 1)
    pages = jnp.where(need, free_stack[idx], -1)
    return pages, free_count - need.sum(dtype=jnp.int32)


def push_pages(free_stack, free_count, pages):
    """Push the valid (>= 0) entries of ``pages`` onto the free stack;
    -1 entries are skipped.  Returns (free_stack', free_count')."""
    valid = pages >= 0
    ranks = jnp.cumsum(valid.astype(jnp.int32)) - 1
    # invalid entries target one-past-the-end and are dropped
    idx = jnp.where(valid, free_count + ranks, free_stack.shape[0])
    free_stack = free_stack.at[idx].set(pages, mode="drop")
    return free_stack, free_count + valid.sum(dtype=jnp.int32)


# -- traced transitions ------------------------------------------------------

def write_prompt(state, slot, k_new, v_new, length, shared_ids, shared_n,
                 dk_new=None, dv_new=None, window=None, keep_from=None):
    """Map + fill one admitted request's cache pages.

    ``k_new``/``v_new`` ``[layers, Sb, nkv, hd]`` hold prefill K/V for
    absolute positions ``[shared_n * page_size, shared_n * page_size +
    Sb)`` (a full-prompt bucket on a prefix miss, the suffix bucket on a
    prefix hit — full-page-only sharing keeps the boundary aligned).
    Pages ``[0, shared_n)`` of the slot's table row come from
    ``shared_ids`` (already resident read-only prefix pages); pages
    ``[shared_n, ceil(length / page_size))`` are popped off the free
    stack and written — so insert costs O(prompt_len) pages, never
    O(S_max).  Traced; ``slot``/``length``/``shared_n`` are traced
    scalars so ONE executable per bucket serves every slot and every
    prefix split.  Returns ``(state, row)`` — the row is fetched by the
    engine to register/refcount pages host-side.

    ``dk_new``/``dv_new`` (speculative engines only): the DRAFT model's
    prefill K/V for the same positions, scattered into ``dkp``/``dvp``
    at the same page ids — the shared table row keeps both pools'
    extents in lockstep.

    ``window`` (a window engine only): ``(layers, wshared_ids, w_from,
    w_pin)``.  ``layers`` is the static pair (CacheGeometry.full_layers,
    .window_layers): ``k_new``/``v_new`` hold every layer of the model and
    each pool takes its own.  The window pool's row keeps table indices
    ``[w_from, ceil(length / page_size))`` only: below ``shared_n`` from
    ``wshared_ids``, the rest popped off the window pool's free stack and
    written.  What ``wshared_ids`` maps below ``w_from`` is dropped from
    the row, and pushed back where it is the lane's own (index >=
    ``w_pin``: pages an earlier chunk of the same prompt wrote, now
    behind the window).  Returns ``(state, rows [2, pages_per_slot])``,
    the full pool's row and the window pool's."""
    if window is not None:
        layers, wshared_ids, w_from, w_pin = window
        sub = dict(state, kp=state["wkp"], vp=state["wvp"],
                   ptab=state["wtab"], free_stack=state["wfree_stack"],
                   free_count=state["wfree_count"])
        full_idx, win_idx = (jnp.asarray(i, jnp.int32) for i in layers)
        j = jnp.arange(state["wtab"].shape[1], dtype=jnp.int32)
        gone = (j < shared_n) & (j < w_from) & (j >= w_pin) \
            & (wshared_ids >= 0)
        sub, wrow = write_prompt(
            sub, slot, k_new[win_idx], v_new[win_idx], length,
            jnp.where(j >= w_from, wshared_ids, -1), shared_n,
            keep_from=jnp.asarray(w_from, jnp.int32))
        wfree_stack, wfree_count = push_pages(
            sub["free_stack"], sub["free_count"],
            jnp.where(gone, wshared_ids, -1))
        state, row = write_prompt(state, slot, k_new[full_idx],
                                  v_new[full_idx], length, shared_ids,
                                  shared_n)
        state = dict(state, wkp=sub["kp"], wvp=sub["vp"], wtab=sub["ptab"],
                     wfree_stack=wfree_stack, wfree_count=wfree_count,
                     w_released=state["w_released"]
                     + gone.sum(dtype=jnp.int32))
        return state, jnp.stack([row, wrow])
    kp, vp = state["kp"], state["vp"]
    L, num_pages, ps = kp.shape[0], kp.shape[1], kp.shape[2]
    pps = state["ptab"].shape[1]
    Sb = k_new.shape[1]
    n_pb = -(-Sb // ps)                     # static: pages k_new spans
    slot = jnp.asarray(slot, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    shared_n = jnp.asarray(shared_n, jnp.int32)

    n_total = (length + ps - 1) // ps       # traced: pages the prompt needs
    j = jnp.arange(pps, dtype=jnp.int32)
    priv = (j >= shared_n) & (j < n_total)
    if keep_from is not None:   # the window pool's row starts there
        priv = priv & (j >= keep_from)
    pages, free_count = take_pages(state["free_stack"],
                                   state["free_count"], priv)
    row = jnp.where(j < shared_n, shared_ids, pages)

    # scatter k_new's page view into the freshly mapped private pages;
    # chunk t covers table index shared_n + t, chunks past the prompt's
    # last page target one-past-the-pool and are dropped
    t = jnp.arange(n_pb, dtype=jnp.int32)
    pj = shared_n + t
    tgt = jnp.where(pj < n_total,
                    row[jnp.clip(pj, 0, pps - 1)], num_pages)
    tgt = jnp.where(tgt < 0, num_pages, tgt)    # a column the row dropped

    def to_pages(x, n_layers):
        pad = jnp.zeros((n_layers, n_pb * ps) + x.shape[2:], kp.dtype)
        pad = pad.at[:, :Sb].set(x.astype(kp.dtype))
        return pad.reshape((n_layers, n_pb, ps) + x.shape[2:])

    kp = kp.at[:, tgt].set(to_pages(k_new, L), mode="drop")
    vp = vp.at[:, tgt].set(to_pages(v_new, L), mode="drop")
    ptab = state["ptab"].at[slot].set(row)
    state = dict(state, kp=kp, vp=vp, ptab=ptab, free_count=free_count)
    if dk_new is not None:
        dL = state["dkp"].shape[0]
        dkp = state["dkp"].at[:, tgt].set(to_pages(dk_new, dL),
                                          mode="drop")
        dvp = state["dvp"].at[:, tgt].set(to_pages(dv_new, dL),
                                          mode="drop")
        state = dict(state, dkp=dkp, dvp=dvp)
    return state, row


def admit_slot(state, slot, tok, length, rng_key, do_sample, temp, top_k,
               stop_pos, eos, pinned, active=True):
    """Arm lane ``slot``: pending token ``tok`` (the first generated
    token, sampled from the prefill logits) will be written at position
    ``length`` on the next decode iteration; table indices below
    ``pinned`` are shared prefix pages the device never frees.  Traced
    scalar args.  ``active`` (traced bool) lets chunked prefill run the
    same executable for every chunk while only the FINAL chunk arms the
    lane — earlier chunks keep it parked with the registers staged."""
    slot = jnp.asarray(slot, jnp.int32)
    return dict(
        state,
        tok=state["tok"].at[slot].set(jnp.asarray(tok, jnp.int32)),
        pos=state["pos"].at[slot].set(jnp.asarray(length, jnp.int32)),
        active=state["active"].at[slot].set(jnp.asarray(active, bool)),
        rng=state["rng"].at[slot].set(rng_key),
        pinned=state["pinned"].at[slot].set(
            jnp.asarray(pinned, jnp.int32)),
        do_sample=state["do_sample"].at[slot].set(
            jnp.asarray(do_sample, bool)),
        temp=state["temp"].at[slot].set(jnp.asarray(temp, jnp.float32)),
        top_k=state["top_k"].at[slot].set(jnp.asarray(top_k, jnp.int32)),
        stop_pos=state["stop_pos"].at[slot].set(
            jnp.asarray(stop_pos, jnp.int32)),
        eos=state["eos"].at[slot].set(jnp.asarray(eos, jnp.int32)),
    )


def release_slots(state, mask):
    """Deactivate the masked lanes (retire / cancel / deadline-preempt)
    and push their PRIVATE pages (table index >= the lane's ``pinned``
    register) back onto the free stack; shared prefix pages stay
    resident for the prefix cache, returned later via
    ``reclaim_pages`` when their host refcount drops to zero."""
    out = dict(state, active=state["active"] & ~mask)
    pools = [("ptab", "free_stack", "free_count")]
    if "wtab" in state:
        pools.append(("wtab", "wfree_stack", "wfree_count"))
    for tab, stack, cnt in pools:
        ptab = state[tab]
        col = jnp.arange(ptab.shape[1], dtype=jnp.int32)[None, :]
        freeable = mask[:, None] & (ptab >= 0) \
            & (col >= state["pinned"][:, None])
        out[stack], out[cnt] = push_pages(
            state[stack], state[cnt],
            jnp.where(freeable, ptab, -1).reshape(-1))
        out[tab] = jnp.where(mask[:, None], -1, ptab)
    return out


def slide_window(state, wtab, wfree_count, pos, active, window):
    """Let go of what lies wholly behind the window of the lanes' next
    query (at ``pos``): table columns whose last token is at or before
    ``pos - window`` leave the row, and the lane's own among them (index
    >= ``pinned``) go back on the window pool's free stack.  Returns
    (wtab, wfree_stack, wfree_count, pages pushed)."""
    ps = state["wkp"].shape[2]
    col = jnp.arange(wtab.shape[1], dtype=jnp.int32)[None, :]
    behind = active[:, None] & (wtab >= 0) \
        & ((col + 1) * ps <= (pos - window + 1)[:, None])
    own = behind & (col >= state["pinned"][:, None])
    wfree_stack, wfree_count = push_pages(
        state["wfree_stack"], wfree_count,
        jnp.where(own, wtab, -1).reshape(-1))
    return jnp.where(behind, -1, wtab), wfree_stack, wfree_count, \
        own.sum(dtype=jnp.int32)


def reclaim_pages(state, pages, wpages=None):
    """Return evicted prefix-cache pages (int32, -1-padded) to the free
    stack — the host calls this once a shared page's refcount hits zero
    (entry evicted AND no slot still reading it).  ``wpages``: the window
    pool's, for a state that has one."""
    free_stack, free_count = push_pages(
        state["free_stack"], state["free_count"], pages)
    state = dict(state, free_stack=free_stack, free_count=free_count)
    if wpages is not None:
        wfree_stack, wfree_count = push_pages(
            state["wfree_stack"], state["wfree_count"], wpages)
        state = dict(state, wfree_stack=wfree_stack, wfree_count=wfree_count)
    return state
