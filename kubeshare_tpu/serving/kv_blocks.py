"""Block-paged KV cache: fixed-size blocks, block tables, free-list allocator.

The dense cache (``models/decoding.init_kv_cache``) reserves
``max_seq_len`` cache rows per batch row for the whole request lifetime —
on a fractional-HBM pod that is the dominant allocation, and almost all
of it is dead (a 40-token answer in a 2048-slot cache).  Here the cache
is a static pool of fixed-size BLOCKS; each serving slot owns an ordered
block table mapping its virtual token positions onto pool blocks, and a
free-list allocator hands blocks out per request and takes them back at
retirement — the cell allocator's reserve/reclaim discipline
(``cell/allocator.py``) applied to HBM rows instead of chip fractions:
reservation is explicit and up-front, release is loud about double
frees, and exhaustion is an admission failure, never a silent
clamp-overwrite.

Everything device-side stays static-shaped: the pool tensors never grow,
block tables are fixed-width int32, and the allocator is pure host-side
bookkeeping — XLA never sees a shape change, so the serving engine's
steps compile once.

Block 0 is RESERVED as a scratch block: jitted steps route the writes of
inactive slots there (a lane that must execute under jit but whose
result must land nowhere).  The allocator never hands block 0 out.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import TransformerConfig


class BlockExhausted(RuntimeError):
    """The pool cannot fund a reservation.  Raised at ADMISSION time —
    the caller queues or rejects the request; nothing mid-flight is ever
    clamped or overwritten."""


class QuotaExceeded(BlockExhausted):
    """A reservation fits the POOL but not the requesting TENANT's
    KV-HBM block quota (and the tenant's own idle-cached blocks, once
    drained, still don't make room).  Distinct from
    :class:`BlockExhausted` so the engine can skip just this tenant and
    keep admitting others — a per-tenant limit must never become
    head-of-line blocking for the whole pool."""


@dataclass(frozen=True)
class KVRowLayout:
    """What ONE token caches in ONE pool layer, as a value: the pool's
    two arrays are ``[layers, num_blocks, *k_row[:1], block_size,
    *k_row[1:]]`` and the same with ``v_row``.

    - ``"kv_heads"``: a K and a V ``[kv_heads, head_dim]`` pair a model
      layer (MHA / MQA / GQA) — of the layers that HAVE a cache row: where
      a model's layers name their operator
      (``TransformerConfig.layer_operators``) the pool holds the attention
      layers' rows only, pool layer ``operator_index(layer)[1]``, and a
      convolution's state lives by slot beside it
      (:func:`init_conv_states`).  Heads narrower than a 128-lane vector
      register lie ``heads_paired`` side by side in one array row
      (``[kv_heads / paired, paired x head_dim]``: two 64-wide heads a
      row), K heads beside K heads and V beside V, so the pool looks to
      every step program like one of ``kv_heads / paired`` heads of 128
      values — written whole a row by ``paged._write_rows``, read by
      ``paged._layer_views`` and by the paged decode kernel as any
      128-wide head is.  A query head is laid into its KV head's part of
      a row of zeros (the products with the other head's values are
      exact zeros) and its context is that part of the result
      (``paged._paired_queries`` / ``_paired_context``), the trick the
      latent kernel plays with its rotary query.  The other choice, a
      head's K beside its V, would need one array where every program
      takes two, and a second softmax path; a pool of 64-wide rows is
      none: the compiler keeps so narrow an array blocks-minor and
      re-lays it in and out of every program, and row writes through a
      64-wide window are a loop of one update a row (see below).
    - ``"latent"``: one latent row an attention SUB-layer, shared by all
      query heads — ``k`` holds its ``kv_lora_rank`` latent values, ``v``
      its ``qk_rope_head_dim`` rotary-key values, the head axis 1.  The
      V rows of ``v_packed`` consecutive layers lie side by side in one
      row of the V array (``[ceil(layers / v_packed), ..., v_packed x
      width]``; where the count of sub-layers is odd the last row's
      second half is spare, and counted): a row of 64 values is half a
      TPU vector register, and the compiler keeps an array that narrow
      blocks-minor — it would transpose the whole array into and out
      of every program.  A packed row is WRITTEN whole as well
      (``paged._write_rows`` reads the rows, lays the sub-layer's key
      into its lanes and scatters all ``v_packed x width`` values back):
      a scatter whose window is narrower than the array's row becomes a
      loop of one update a row, 4.5-5.0 us each on a v5e against 0.09 us
      a whole row (PERF.md, PR 42).

    A model whose layers name the "window" attention kind
    (``TransformerConfig.layer_operators``) caches BY LAYER KIND:
    ``window_layers`` of the ``layers`` keep their rows in a pool of their
    own (:func:`init_paged_pool`), under a table and an allocator of their
    own, and the pages a window no longer reaches go back while the request
    runs.  The row is the same in both kinds.

    Every step program writes rows through ``paged._write_rows`` and
    reads them through ``paged._layer_views`` whatever the layout; what
    packs a block for another tier or another pool (``kv_tier``,
    ``fabric``, ``disagg``) or shards the head axis (``sharded``) still
    assumes ``"kv_heads"`` and says so (:func:`require_kv_heads`).
    """

    kind: str
    layers: int
    k_row: Tuple[int, int]  # (heads, width)
    v_row: Tuple[int, int]
    v_packed: int = 1
    # a 'retention' block's rows also hold the log gate, one float32 a KV
    # head a layer, in an array of its own (PagedKVPool.gate)
    gate_heads: int = 0
    # KV heads that share one array row ("kv_heads" only; 1: a row a head)
    heads_paired: int = 1
    # of ``layers``, those of the window kind ("kv_heads" only; 0: one kind)
    window_layers: int = 0

    @property
    def v_layers(self) -> int:
        """Layers of the V array: ``v_packed`` pool layers share one."""
        return -(-self.layers // self.v_packed)

    @property
    def kind_layers(self) -> Tuple[int, ...]:
        """The layers of each kind's pool, the full kind first: one entry
        where the model caches under one table a lane."""
        if not self.window_layers:
            return (self.layers,)
        return (self.layers - self.window_layers, self.window_layers)

    def values_per_row(self) -> int:
        """Values the pool holds a token, over all pool layers (a spare
        half row of an odd count of packed layers among them, the log
        gates not: they are float32 whatever the rows are)."""
        return (self.layers * self.k_row[0] * self.k_row[1]
                + self.v_layers * self.v_packed
                * self.v_row[0] * self.v_row[1])

    def block_shapes(self, block_size: int):
        """One block's K and V slabs, all layers."""
        return ((self.layers, self.k_row[0], block_size, self.k_row[1]),
                (self.v_layers, self.v_row[0], block_size,
                 self.v_row[1] * self.v_packed))


def kv_row_layout(config: TransformerConfig) -> KVRowLayout:
    if config.latent:
        # one row an attention sub-layer, as many as the model has
        return KVRowLayout("latent", config.attn_sublayers,
                           (1, config.kv_lora_rank),
                           (1, config.qk_rope_head_dim), v_packed=2)
    paired = heads_paired(config)
    row = (config.kv_heads // paired, config.head_dim * paired)
    return KVRowLayout(
        "kv_heads", config.attn_sublayers, row, row,
        gate_heads=config.kv_heads if config.block == "retention" else 0,
        heads_paired=paired, window_layers=config.window_layers)


LANES = 128  # values a TPU vector register holds across


def heads_paired(config: TransformerConfig) -> int:
    """KV heads an array row of the pool holds side by side: as many as
    fill a 128-lane register where a model whose layers name their
    operator has narrower heads (two at head width 64) and its KV heads
    group so, else 1.  The other blocks' step programs take a row a head."""
    hd = config.head_dim
    if config.layer_operators is None or hd >= LANES or LANES % hd \
            or config.kv_heads % (LANES // hd):
        return 1
    return LANES // hd


def require_kv_heads(config: TransformerConfig, who: str) -> None:
    """Raise for a cache row ``who`` cannot serve yet."""
    layout = kv_row_layout(config)
    if layout.window_layers:
        raise ValueError(
            f"{who} serves one pool under one table a lane; this model "
            f"caches BY LAYER KIND ({layout.window_layers} of its "
            f"{layout.layers} attention layers keep a window's rows in a "
            f"pool of their own)")
    if layout.kind != "kv_heads":
        raise ValueError(
            f"{who} serves the 'kv_heads' row layout only (a K and a V "
            f"[kv_heads, head_dim] pair a layer); block {config.block!r} "
            f"caches the {layout.kind!r} layout (one row of "
            f"{layout.k_row[1]} + {layout.v_row[1]} values a sub-layer, "
            f"no heads)")


@dataclass(frozen=True)
class PagedKVPool:
    """The static device-side block pool.

    ``k``/``v``: [layers, num_blocks, heads, block_size, width] as the
    model's :class:`KVRowLayout` says (``[n_layers, num_blocks,
    kv_heads, block_size, head_dim]`` both, for the dense block)
    — one cache row per (block, offset) pair; a slot's virtual position
    ``p`` lives at block ``table[p // block_size]``, offset
    ``p % block_size``.

    A model that caches by layer kind (:class:`KVRowLayout`
    ``window_layers``) holds an array a KIND, ``k`` and ``v`` each a tuple
    (full, window): the same row, each kind's own layers and its own count
    of blocks (:func:`kind_blocks`), block 0 of each its scratch block.
    Every step program takes and returns the tuples as it takes the arrays.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    block_size: int
    # a 'retention' block's log gate a row: float32 [layers, kv_heads,
    # num_blocks x block_size], paged with K and V (a row's column is
    # page x block_size + offset; the long axis last, so that the array's
    # own layout has no padding); None elsewhere
    gate: Optional[jnp.ndarray] = None

    @property
    def by_kind(self) -> bool:
        return isinstance(self.k, tuple)

    @property
    def num_blocks(self) -> int:
        """Blocks of the pool (the full kind's, where it holds two)."""
        return jax.tree_util.tree_leaves(self.k)[0].shape[1]

    @property
    def kind_num_blocks(self) -> Tuple[int, ...]:
        """Blocks of each kind's pool, scratch block 0 included."""
        return tuple(x.shape[1] for x in jax.tree_util.tree_leaves(self.k))

    def arrays(self) -> Tuple[jnp.ndarray, ...]:
        """The pool's device arrays, in the order every step program
        takes and returns them."""
        return tuple(jax.tree_util.tree_leaves((self.k, self.v, self.gate)))

    def bytes_per_block(self) -> int:
        """HBM cost of one block (K and V and, where the block has one,
        the log gate; all layers) — the allocation granularity the
        serving docs size against (a pool under one table a lane: a pool by
        layer kind has a block a kind)."""
        return sum(x.size * x.dtype.itemsize
                   for x in self.arrays()) // self.num_blocks

    def read_block(self, block: int) -> Tuple[np.ndarray, np.ndarray]:
        """Host snapshot of one block's K and V slabs, each
        ``[n_layers, kv_heads, block_size, head_dim]`` — the export
        half of KV migration (the pack side feeds these straight into
        ``kv_tier.pack_block``).  Reading synchronizes with any
        in-flight dispatch writing the pool; callers on the pipelined
        hot path meter that stall."""
        return (np.asarray(self.k[:, block]), np.asarray(self.v[:, block]))

    def read_chain(
        self, blocks: Sequence[int], pad_to: Optional[int] = None
    ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Host snapshot of a whole block chain in ONE gather + ONE
        device-to-host transfer per tensor — per-block (K, V) slab
        pairs shaped like :meth:`read_block`'s.  The migration pack
        walks entire chains, and a per-block read would pay one
        pool-write sync per block; here the chain pays it once.
        ``pad_to`` (e.g. the slot table width) fixes the gather's index
        shape so it compiles ONCE instead of once per chain length —
        the padding rows re-read block 0 and are dropped host-side."""
        idx = list(blocks)
        n = len(idx)
        if pad_to is not None and pad_to > n:
            idx = idx + [0] * (pad_to - n)
        gather = jnp.asarray(idx, jnp.int32)
        k_all = np.asarray(self.k[:, gather])  # [n_layers, n, heads, bs, hd]
        v_all = np.asarray(self.v[:, gather])
        return [(k_all[:, i], v_all[:, i]) for i in range(n)]


def chain_token_runs(tokens, block_size: int) -> List[List[int]]:
    """Split a token sequence into per-block runs: run ``i`` holds the
    tokens whose K/V rows live in the chain's ``i``-th block (the last
    run may be partial).  The migration pack walks a slot's table with
    exactly these runs — one ``pack_block`` frame per block."""
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    toks = [int(t) for t in tokens]
    if not toks:
        raise ValueError("cannot split an empty token sequence")
    return [toks[i: i + block_size]
            for i in range(0, len(toks), block_size)]


def window_reserve_rows(window: int, block_size: int,
                        dispatch_rows: int) -> int:
    """Rows a lane of the window kind is funded for, whatever its request's
    length: a dispatch that adds ``a`` rows from row ``n`` on attends rows
    ``n - window + 1 .. n + a - 1``, ``window + a - 1`` of them, which
    whole pages cover with one more where neither end lies on a page's
    edge."""
    pages = -(-(window + dispatch_rows - 1) // block_size) + 1
    return pages * block_size


def kind_blocks(layout: KVRowLayout, num_blocks: int, block_size: int,
                max_request_len: int, dispatch_rows: int,
                window: int) -> Tuple[int, ...]:
    """How a pool by layer kind divides ``num_blocks`` blocks of EVERY
    layer's row between its kinds: (N_full, N_window), each with its own
    scratch block 0, holding the same bytes —

        g x N_full + w x N_window = (g + w) x num_blocks

    with ``g`` full and ``w`` window layers of one row width.  The law: both
    kinds fund the same number of worst-case lanes, a lane holding
    ``max_request_len`` rows of the full kind and
    :func:`window_reserve_rows` of the window kind (what admission reserves
    of each).  ``N_window`` is moved to the nearest count where ``w x
    (num_blocks - N_window)`` divides by ``g``, so ``N_full`` is whole and
    no byte is left over."""
    g, w = layout.kind_layers
    full = -(-max_request_len // block_size)
    near = min(full, window_reserve_rows(window, block_size, dispatch_rows)
               // block_size)
    lanes = (g + w) * (num_blocks - 1) / (g * full + w * near)
    # the nearest count to the law's that leaves both kinds two blocks (a
    # scratch block and one more) and N_full whole: within g of it
    most = num_blocks + (num_blocks - 2) * g // w
    target = max(2, min(int(1 + lanes * near), most))
    fits = [n for delta in range(g + 1) for n in (target - delta,
                                                  target + delta)
            if 2 <= n <= most and w * (num_blocks - n) % g == 0]
    if not fits:
        raise ValueError(
            f"num_blocks {num_blocks} cannot be divided between "
            f"{g} full and {w} window layers (each kind needs its scratch "
            f"block and one more)")
    n_window = fits[0]
    n_full = num_blocks + w * (num_blocks - n_window) // g
    return n_full, n_window


def init_paged_pool(
    config: TransformerConfig, num_blocks: int, block_size: int,
    kv_sharding=None, kinds: Optional[Tuple[int, ...]] = None,
) -> PagedKVPool:
    """Allocate the static block pool (block 0 is the scratch block, so
    ``num_blocks - 1`` are allocatable).  ``kinds``: the blocks of each
    kind's pool where the model caches by layer kind (:func:`kind_blocks`,
    which the engine asks with what it serves; the bytes are ``num_blocks``
    blocks of every layer's row whatever the division).

    ``kv_sharding``: optional ``jax.sharding.Sharding`` the buffers are
    committed to — the sharded serving context passes a
    ``NamedSharding`` splitting the KV-head axis over its ``tp`` mesh,
    so each device materializes only its head shard.  Host reads
    (:meth:`PagedKVPool.read_block` / :meth:`read_chain`) gather
    transparently through ``np.asarray``, so tiering and migration are
    sharding-agnostic."""
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    if num_blocks < 2:
        raise ValueError(
            f"num_blocks must be >= 2 (block 0 is reserved scratch), "
            f"got {num_blocks}"
        )
    layout = kv_row_layout(config)
    if layout.window_layers:
        if kv_sharding is not None:
            raise ValueError("a pool by layer kind takes no kv_sharding")
        if kinds is None:
            # no engine says what its requests and dispatches may be: as
            # for requests of max_seq_len rows and dispatches of a page
            kinds = kind_blocks(layout, num_blocks, block_size,
                                config.max_seq_len, block_size,
                                config.attention_window)
        k, v = (tuple(jnp.zeros((layers, blocks) + shape[1:], config.dtype)
                      for layers, blocks in zip(layout.kind_layers, kinds))
                for shape in layout.block_shapes(block_size))
        return PagedKVPool(k=k, v=v, block_size=block_size)
    k, v = (jnp.zeros(shape[:1] + (num_blocks,) + shape[1:], config.dtype)
            for shape in layout.block_shapes(block_size))
    if kv_sharding is not None:
        k = jax.device_put(k, kv_sharding)
        v = jax.device_put(v, kv_sharding)
    gate = None
    if layout.gate_heads:
        gate = jnp.zeros((layout.layers, layout.gate_heads,
                          num_blocks * block_size), jnp.float32)
    return PagedKVPool(k=k, v=v, block_size=block_size, gate=gate)


def init_retention_states(config: TransformerConfig,
                          num_slots: int) -> Tuple[jnp.ndarray, ...]:
    """The recurrent states of a 'retention' block, BY SLOT and beside
    the pool, not in it: one float32 array a layer, ``[num_slots,
    kv_heads, state_rows, phi_width]`` — a lane's ``[S | z]`` a KV head,
    transposed, its head_dim + 1 rows rounded up to whole tiles
    (``ops/retention.py``), as of the lane's fold point.  An array a layer,
    not one array of all: a layer's window of a stacked array is a copy
    (1.2 GB a layer a step at the cell's size), a whole array is not.  The
    paged pool holds a lane's UNFOLDED rows only.  A slot's state means
    nothing until its lane has folded a key block (the first fold writes
    it whole), so nothing is zeroed between requests."""
    from ..ops.retention import phi_width, state_rows

    return tuple(
        jnp.zeros((num_slots, config.kv_heads, state_rows(config.head_dim),
                   phi_width(config.head_dim)), jnp.float32)
        for _ in range(config.n_layers))


def init_conv_states(config: TransformerConfig,
                     num_slots: int) -> Tuple[jnp.ndarray, ...]:
    """The short convolutions' states, BY SLOT and beside the pool, not in
    it: one array a convolution layer, ``[num_slots, conv_taps - 1,
    d_model]`` in the served dtype — the last ``conv_taps - 1`` rows of
    ``B * u`` the slot's lane has seen (``ops/short_conv.py``), 48 KB a
    lane over six layers at d 2048.  An array a layer, as the retention
    block's: a step program replaces each whole.  A chunk that starts at
    row 0 reads zeros whatever the slot holds, so nothing is zeroed
    between requests."""
    return tuple(
        jnp.zeros((num_slots, config.conv_taps - 1, config.d_model),
                  config.dtype)
        for _ in range(config.conv_layers))


class BlockAllocator:
    """Refcounted free-list allocator over pool block ids (host-side).

    A block is in exactly one of three states:

    - **free** — on the free list, immediately reservable (LIFO reuse:
      the blocks a retired request returns are the first handed to the
      next admission — the hot end of the pool stays hot);
    - **in use** — refcount >= 1.  With the prefix cache, a SHARED
      prefix block is referenced by every slot whose page table maps it
      (``retain``/``reclaim`` move the count); a block is never handed
      back out while anyone still reads it;
    - **idle-cached** — refcount 0 but still referenced by the prefix
      index.  These sit in an LRU pool (``cached_idle_blocks``) that
      eviction drains ONLY when ``reserve`` would otherwise raise
      :class:`BlockExhausted` — the cache uses exactly the HBM that
      admission doesn't need, and gives it back the moment it does.

    Eviction is delegated to ``evictor`` (the engine's wrapper over the
    prefix index — or over the host tier's demotion path, when KV
    tiering is on): called as ``evictor(victim, reason)`` where
    ``reason`` names the trigger (``"reservation_pressure"`` for the
    shortfall drain, ``"quota_drain"`` for a tenant's own-cache drain —
    the metrics plane's eviction-``reason`` label), it must release the
    victim's DEVICE block (and its subtree's — an idle parent's
    descendants are idle too, because every reader retains the full
    chain) and return every block released, whether the blocks' K/V
    was destroyed or demoted host-side.  The allocator verifies each
    returned block really was idle-cached; a live block coming back
    from the evictor is a corruption, not a policy choice.

    **Tenant charging** (the QoS subsystem's HBM ledger): a reservation
    made with ``tenant=`` charges every granted block to that tenant
    until the block returns to the free list — through its in-use life
    AND any idle-cached afterlife (a cached block still occupies HBM
    attributable to whoever brought it in).  ``retain`` does NOT move
    the charge: a prefix block shared across tenants is charged once,
    to the tenant that paid its prefill.  A ``quota=`` reservation that
    would push the tenant's charge over its cap first drains the
    tenant's OWN idle-cached blocks (its cache must never wedge its own
    quota), then raises :class:`QuotaExceeded`.  A Guarantee tenant's
    reservation passes ``evict_tenants_first=`` (the opportunistic
    tenant set) so the LRU drain reclaims idle-cached blocks charged to
    Opportunistic tenants before touching anyone else's — the paper's
    class asymmetry applied to cache HBM.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 evictor: Optional[Callable[[int, str], List[int]]] = None
                 ) -> None:
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is reserved scratch), "
                f"got {num_blocks}"
            )
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.evictor = evictor
        # block 0 reserved; free list popped from the tail (LIFO)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}  # block id -> reference count
        self._cached: Set[int] = set()  # blocks the prefix index holds
        # refcount-0 cached blocks, least recently released first
        self._idle: "OrderedDict[int, None]" = OrderedDict()
        self.evicted_blocks = 0  # lifetime eviction counter (metrics)
        # QoS charge ledger: block id -> charged tenant, tenant -> blocks
        # charged (in-use + idle-cached); empty when nobody passes tenant=
        self._tenant_of: Dict[int, str] = {}
        self._usage: Dict[str, int] = {}
        self._lock = threading.Lock()

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        with self._lock:
            return len(self._refs)

    @property
    def cached_idle_blocks(self) -> int:
        with self._lock:
            return len(self._idle)

    @property
    def available_blocks(self) -> int:
        """What a reservation can draw on: free now + evictable cache."""
        with self._lock:
            return len(self._free) + len(self._idle)

    def blocks_for_tokens(self, tokens: int) -> int:
        """How many blocks cover ``tokens`` cache rows."""
        return -(-tokens // self.block_size)

    def tenant_usage(self, tenant: str) -> int:
        """Blocks currently charged to ``tenant`` (in-use + idle-cached)."""
        with self._lock:
            return self._usage.get(tenant, 0)

    def quota_can_fit(self, count: int, tenant: str, quota: Optional[int],
                      keep: Sequence[int] = ()) -> bool:
        """Dry-run quota check: could ``reserve(count, tenant=, quota=)``
        pass the quota gate, counting the tenant's drainable own-cache
        headroom but EXCLUDING ``keep`` (blocks the caller is about to
        retain, so the drain could not touch them)?  Side-effect-free —
        the engine consults this before preempting a victim for a
        Guarantee head, because preemption cannot cure a quota block."""
        if quota is None:
            return True
        keep_set = set(keep)
        with self._lock:
            drainable = sum(
                1 for b in self._idle
                if self._tenant_of.get(b) == tenant and b not in keep_set)
            return (self._usage.get(tenant, 0) - drainable + count
                    <= quota)

    @property
    def usage_by_tenant(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._usage)

    def _uncharge_locked(self, block: int) -> None:
        tenant = self._tenant_of.pop(block, None)
        if tenant is not None:
            self._usage[tenant] -= 1
            if not self._usage[tenant]:
                del self._usage[tenant]

    def _evict_locked(self, victim: int, reason: str) -> None:
        """Detach ``victim`` (and its subtree, via the evictor) from the
        cache: every released block moves idle -> free and drops its
        tenant charge — whether the evictor destroyed the K/V or
        demoted it host-side, the DEVICE HBM (and the tenant's quota
        charge for it) is given back either way.  ``reason`` names the
        trigger for the metrics plane.  Caller holds the lock and has
        verified the victim is idle-cached."""
        removed = (self.evictor(victim, reason) if self.evictor is not None
                   else [victim])
        if victim not in removed:
            raise RuntimeError(
                f"evictor did not release victim block {victim}")
        for b in removed:
            if b in self._refs or b not in self._idle:
                raise RuntimeError(
                    f"evictor released block {b}, which is not "
                    f"idle-cached (refcount "
                    f"{self._refs.get(b, 0)}) — index/allocator "
                    f"state diverged")
            del self._idle[b]
            self._cached.discard(b)
            self._uncharge_locked(b)
            self._free.append(b)
            self.evicted_blocks += 1

    def reserve(self, count: int, owner: str,
                tenant: Optional[str] = None,
                quota: Optional[int] = None,
                evict_tenants_first: Optional[Set[str]] = None
                ) -> List[int]:
        """Hand out ``count`` blocks or fail LOUDLY with the shortfall.

        All-or-nothing: a partial grant would leave a request half-
        admitted with no block for its next token — exactly the silent
        clamp-overwrite failure mode the dense cache's headroom checks
        exist to prevent.  When the free list alone cannot fund the
        reservation, idle-cached blocks are evicted LRU-first (whole
        subtrees — see class docstring); only a shortfall that survives
        a fully drained cache raises.

        With ``tenant=`` the granted blocks are charged to that tenant;
        ``quota=`` additionally bounds the tenant's total charge — an
        over-quota reservation first drains the tenant's OWN idle-cached
        blocks, then raises :class:`QuotaExceeded` (the pool may still
        be able to fund OTHER tenants).  ``evict_tenants_first`` biases
        the shortfall drain toward blocks charged to those tenants
        (LRU within the preferred set, then plain LRU) — how a
        Guarantee reservation reclaims Opportunistic cache HBM.
        """
        if count < 1:
            raise ValueError(f"count must be >= 1, got {count}")
        with self._lock:
            if tenant is not None and quota is not None:
                used = self._usage.get(tenant, 0)
                if used + count > quota:
                    # the tenant's own cache must never wedge its own
                    # quota: drain its idle-cached blocks (LRU; subtree
                    # granular, so a mixed-charge subtree may release
                    # more) — but ONLY when the drain can actually make
                    # room.  A reservation doomed by the tenant's IN-USE
                    # blocks raises without touching the cache (the same
                    # no-wipe discipline as the pool doomed-check below:
                    # a blocked head retried every tick must not grind
                    # its tenant's hit rate to zero).
                    drainable = sum(
                        1 for b in self._idle
                        if self._tenant_of.get(b) == tenant)
                    if used - drainable + count > quota:
                        raise QuotaExceeded(
                            f"request {owner!r} needs {count} blocks but "
                            f"tenant {tenant!r} holds {used - drainable} "
                            f"in use (+{drainable} cached) of its "
                            f"{quota}-block quota — over even after a "
                            f"full own-cache drain"
                        )
                    for b in [b for b in self._idle
                              if self._tenant_of.get(b) == tenant]:
                        if self._usage.get(tenant, 0) + count <= quota:
                            break
                        if b in self._idle:  # prior subtree may cover it
                            self._evict_locked(b, "quota_drain")
                if self._usage.get(tenant, 0) + count > quota:
                    raise QuotaExceeded(
                        f"request {owner!r} needs {count} blocks but "
                        f"tenant {tenant!r} already holds "
                        f"{self._usage.get(tenant, 0)} of its "
                        f"{quota}-block quota"
                    )
            if count > len(self._free) + len(self._idle):
                # doomed even after a full drain (eviction conserves
                # free + idle) — raise WITHOUT wiping the cache, or a
                # too-big head-of-line request would pin the prefix
                # cache at zero for its whole wait
                raise BlockExhausted(
                    f"request {owner!r} needs {count} blocks but only "
                    f"{len(self._free)} of {self.num_blocks - 1} are free "
                    f"({len(self._idle)} more evictable; block_size "
                    f"{self.block_size})"
                )
            while count > len(self._free) and self._idle:
                victim = next(iter(self._idle))
                if evict_tenants_first:
                    # prefer the coldest idle block charged to a
                    # preferred-victim tenant; fall back to plain LRU
                    for b in self._idle:
                        if self._tenant_of.get(b) in evict_tenants_first:
                            victim = b
                            break
                self._evict_locked(victim, "reservation_pressure")
            # the up-front doomed-check plus the drain loop guarantee
            # the free list can now fund the reservation (eviction
            # conserves free + idle)
            blocks = [self._free.pop() for _ in range(count)]
            for b in blocks:
                self._refs[b] = 1
                if tenant is not None:
                    self._tenant_of[b] = tenant
                    self._usage[tenant] = self._usage.get(tenant, 0) + 1
            return blocks

    def retain(self, blocks: Sequence[int]) -> None:
        """Add one reference per block — a prefix-cache hit mapping
        cached blocks into a new slot's page table.  Retaining an
        idle-cached block pulls it out of the eviction pool."""
        with self._lock:
            for b in blocks:
                if b in self._refs:
                    self._refs[b] += 1
                elif b in self._idle:
                    del self._idle[b]
                    self._refs[b] = 1
                else:
                    raise ValueError(
                        f"block {b} is neither in use nor cached — "
                        f"cannot retain (stale match?)")

    def reclaim(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block.  At refcount 0 a block goes
        back to the free list — unless the prefix index still holds it,
        in which case it parks in the idle-cached LRU pool (most
        recently released last, so eviction drains the coldest prefix
        first).  Double frees and foreign ids raise — a corrupted table
        must never silently donate another request's live blocks."""
        with self._lock:
            for b in blocks:
                if b not in self._refs:
                    raise ValueError(
                        f"block {b} is not allocated (double free, or a "
                        f"corrupted block table)"
                    )
            for b in blocks:
                self._refs[b] -= 1
                if self._refs[b] == 0:
                    del self._refs[b]
                    if b in self._cached:
                        # parks idle-cached: STILL charged to its tenant
                        # (the cache occupies that tenant's HBM budget
                        # until eviction or a free)
                        self._idle[b] = None
                    else:
                        self._uncharge_locked(b)
                        self._free.append(b)

    def mark_cached(self, blocks: Sequence[int]) -> None:
        """The prefix index now references these blocks (retirement
        insertion); at refcount 0 they park instead of freeing."""
        with self._lock:
            for b in blocks:
                if b not in self._refs and b not in self._idle:
                    raise ValueError(
                        f"block {b} is not live — cannot mark cached")
                self._cached.add(b)

    def uncache(self, block: int) -> None:
        """The prefix index dropped this block (a displaced upgrade).
        An idle block frees immediately; an in-use block frees at its
        last reclaim."""
        with self._lock:
            self._cached.discard(block)
            if block in self._idle:
                del self._idle[block]
                self._uncharge_locked(block)
                self._free.append(block)
