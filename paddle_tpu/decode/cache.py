"""Paged KV cache: fixed-size device blocks + a host-side allocator.

The device half (:class:`PagedKVCache`) is two preallocated arrays
``[num_layers, num_blocks, block_tokens, n_head * head_dim]`` — ``[L,
NB, bs, H*Dh]``, keys and values — that ride
:meth:`Executor.run_callable` as donated state: every prefill/decode
dispatch consumes the old buffers and returns the updated ones, so the
cache is resident in device memory for the engine's whole life and no
dispatch ever copies it to host — or on the device.  Heads are merged
into the minor axis so that a block's ``[bs, H*Dh]`` fills whole
(sublane, lane) tiles of the TPU (GPT-1: 16 x 768 f32 = 2 x 6 tiles of
(8, 128)): the array then keeps its row-major layout in HBM, the
per-layer scatters update it in place, and the paged kernel is handed
the WHOLE pool with the layer in its index map (``kernels/attention.py
decode_attention``).  With ``[..., H, Dh]`` = ``(12, 64)`` as minor
dims the device stored the pool blocks-minor and every program relaid
all of it to row-major and back — four copies of the pool per dispatch
(PERF.md, PR 28).  Nothing may slice a layer out (``k[i]``) on a path
that runs per token: index ``k[i, blocks]`` in one gather instead.

The host half (:class:`BlockAllocator`) is a refcounted free list over
block ids.  Block 0 is RESERVED as the trash block: padded prompt
positions and inactive decode slots write their (garbage) K/V there,
which keeps every dispatch a fixed-shape scatter with no branching —
the price of one wasted block buys shape-stable admission/eviction
(the whole point of paging: a request joining or leaving moves
block-table entries, never compiled shapes).

Every allocated block carries a refcount.  With full reservation
(no admission policy) each block has exactly one owner, so ``alloc``/``release`` behave
(and order the free list) exactly as the original single-owner free
list did.  Prefix sharing and beam forking raise refcounts above one:
a block referenced by several streams is immutable to all of them —
writers must fork it (copy-on-write) first.  A zero-refcount block
either returns to the free list or, when a :class:`PrefixCache` claims
it, is *parked* in the cache's LRU so a later prompt with the same
content can revive it without re-prefilling.

Sizing: by default a request admitted with prompt length
P and output budget M reserves ``ceil((P + M) / block_tokens)`` blocks
up front — admission is the only point that can fail for lack of
memory.  An engine built with ``overcommit=True`` reserves only
``ceil((P + 1) / block_tokens)`` and grows one block per step; a
failed growth triggers preemption (engine doc).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp


def blocks_for(tokens: int, block_tokens: int) -> int:
    """Blocks needed to hold ``tokens`` tokens."""
    return max(1, -(-int(tokens) // int(block_tokens)))


_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_MASK64 = (1 << 64) - 1


def _fnv1a64(data: bytes, h: int = _FNV_OFFSET) -> int:
    for byte in data:
        h = ((h ^ byte) * _FNV_PRIME) & _MASK64
    return h


def _fold_token(h: int, token: int) -> int:
    return _fnv1a64(int(token).to_bytes(4, "little", signed=True), h)


class BlockAllocator:
    """Refcounted free-list allocator over cache block ids
    1..num_blocks-1 (block 0 is the reserved trash block — module doc).

    ``alloc`` hands out blocks at refcount 1; ``incref`` adds sharers;
    ``decref``/``release`` drop references.  A block whose refcount
    reaches zero goes back on the free list *in drop order* — with
    single-owner usage this reproduces the original free-list ordering
    byte for byte.  If a :class:`PrefixCache` is attached, zero-ref
    blocks it has registered are parked in its LRU instead of freed.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (1 usable + trash), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self._free: List[int] = list(range(1, self.num_blocks))
        self._ref: Dict[int, int] = {}
        self._prefix_cache: Optional["PrefixCache"] = None

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def referenced_blocks(self) -> int:
        """Blocks with refcount >= 1 (held by at least one stream)."""
        return len(self._ref)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def leaked(self, parked: int = 0) -> int:
        """Pool invariant: usable blocks not free, not referenced and
        not parked in a prefix cache.  Must be zero at all times."""
        return (self.num_blocks - 1 - len(self._free)
                - len(self._ref) - int(parked))

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` block ids at refcount 1, or None (caller queues /
        reclaims / preempts) when short — never a partial grant."""
        if n > len(self._free):
            return None
        out, self._free = self._free[:n], self._free[n:]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, block: int) -> None:
        if block not in self._ref:
            raise ValueError(f"incref of unreferenced block {block}")
        self._ref[block] += 1

    def decref(self, block: int) -> None:
        """Drop one reference; at zero the block is parked in the
        attached prefix cache (if it registered the block) or freed."""
        n = self._ref.get(block, 0)
        if n <= 0:
            raise ValueError(f"decref of unreferenced block {block}")
        if n > 1:
            self._ref[block] = n - 1
            return
        del self._ref[block]
        if self._prefix_cache is not None and self._prefix_cache._park(block):
            return
        self._free.append(block)

    def release(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            if not 1 <= b < self.num_blocks:
                raise ValueError(f"bad block id {b}")
        for b in blocks:
            self.decref(b)


class PrefixCache:
    """Content-addressed registry of full, immutable prompt blocks.

    A block is cacheable once prefill has written all ``block_tokens``
    of its positions from the prompt — from then on its K/V content is
    a pure function of (model identity, token ids up to the block
    boundary), captured by a rolling FNV-1a chain hash.  Admission
    walks the new prompt's block-aligned prefix against the registry
    and adopts hits (incref / revive), so a shared system prompt
    prefills once.

    Entries whose block is still referenced by live streams cost
    nothing; when the last reference drops the allocator *parks* the
    block here (LRU order) instead of freeing it.  ``reclaim`` evicts
    parked blocks back to the free list under pool pressure — a cached
    block is only ever a loan from the free pool.

    Hash hits are verified against the stored token ids before reuse:
    a 64-bit collision can alias two prefixes, and serving another
    stream's K/V would silently corrupt output, so a colliding entry
    is treated as a miss (and counted).
    """

    def __init__(self, allocator: BlockAllocator, block_tokens: int,
                 model_key: str = ""):
        self.allocator = allocator
        self.block_tokens = int(block_tokens)
        self._seed = _fnv1a64(str(model_key).encode("utf-8"))
        # key -> (block id, token ids covered by this block)
        self._entries: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        self._block_key: Dict[int, int] = {}
        # zero-refcount cached blocks, oldest-parked first
        self._lru: "OrderedDict[int, int]" = OrderedDict()
        self.collisions = 0
        allocator._prefix_cache = self

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def parked_blocks(self) -> int:
        return len(self._lru)

    def chain_keys(self, tokens: Sequence[int]) -> List[int]:
        """Rolling hash keyed at each full block boundary of
        ``tokens``: key[i] covers tokens[: (i + 1) * block_tokens]."""
        bs = self.block_tokens
        keys: List[int] = []
        h = self._seed
        for i in range(len(tokens) // bs):
            for t in tokens[i * bs:(i + 1) * bs]:
                h = _fold_token(h, int(t))
            keys.append(h)
        return keys

    def match(self, tokens: Sequence[int], max_blocks: int
              ) -> List[Tuple[int, int]]:
        """Longest cached block-aligned prefix of ``tokens``, capped at
        ``max_blocks`` blocks.  Returns [(key, block)] per hit; stops
        at the first miss (a later block is only valid on top of all
        earlier ones).  Token ids are verified on every hash hit."""
        hits: List[Tuple[int, int]] = []
        bs = self.block_tokens
        toks = [int(t) for t in tokens]
        for i, key in enumerate(self.chain_keys(toks)):
            if len(hits) >= max_blocks:
                break
            ent = self._entries.get(key)
            if ent is None:
                break
            block, covered = ent
            if tuple(toks[:(i + 1) * bs]) != covered:
                self.collisions += 1
                break
            hits.append((key, block))
        return hits

    def acquire(self, key: int) -> int:
        """Take a reference on a matched entry's block (revives it from
        the LRU if parked)."""
        block, _ = self._entries[key]
        if block in self._lru:
            del self._lru[block]
            self.allocator._ref[block] = 1
        else:
            self.allocator.incref(block)
        return block

    def insert(self, key: int, tokens: Sequence[int], block: int) -> bool:
        """Register a freshly prefilled full block under ``key``.  The
        block stays owned by its stream (no extra ref); it parks here
        when the last stream drops it.  First writer wins — an existing
        live entry is kept."""
        if key in self._entries:
            return False
        if block in self._block_key:
            return False
        self._entries[key] = (block, tuple(int(t) for t in tokens))
        self._block_key[block] = key
        return True

    def holds(self, block: int) -> bool:
        """True if writing into ``block`` must fork it (its content is
        advertised to future admissions)."""
        return block in self._block_key

    def _park(self, block: int) -> bool:
        """Allocator callback: keep this zero-ref block cached (LRU)
        instead of freeing it.  False if the block is not registered."""
        if block not in self._block_key:
            return False
        self._lru[block] = block
        self._lru.move_to_end(block)
        return True

    def _drop_entry(self, block: int) -> None:
        key = self._block_key.pop(block)
        del self._entries[key]

    def reclaim(self, n_blocks: int) -> int:
        """Evict up to ``n_blocks`` parked blocks (oldest first) back
        to the free list.  Returns how many were freed."""
        freed = 0
        while freed < n_blocks and self._lru:
            block, _ = self._lru.popitem(last=False)
            self._drop_entry(block)
            self.allocator._free.append(block)
            freed += 1
        return freed

    def snapshot(self) -> dict:
        return {
            "entries": len(self._entries),
            "parked_blocks": len(self._lru),
            "collisions": self.collisions,
        }


class _PagedPool:
    """What every paged pool shares: the block geometry, the
    :class:`BlockAllocator` and the base of ``snapshot()``.  A subclass
    allocates its arrays and gives ``nbytes``, ``state()`` and
    ``update()``."""

    kind = "kv"
    quantized = False

    def __init__(self, num_layers: int, num_blocks: int,
                 block_tokens: int, dtype):
        self.num_layers = int(num_layers)
        self.num_blocks = int(num_blocks)
        self.block_tokens = int(block_tokens)
        if self.block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1, got "
                             f"{self.block_tokens}")
        self.dtype = str(dtype)
        self.allocator = BlockAllocator(self.num_blocks)

    def max_context(self, max_blocks_per_seq: int) -> int:
        return max_blocks_per_seq * self.block_tokens

    def snapshot(self) -> dict:
        snap = {
            "num_blocks": self.num_blocks,
            "block_tokens": self.block_tokens,
            "free_blocks": self.allocator.free_blocks,
            "bytes": self.nbytes,
        }
        if self.kind != "kv":
            snap["kind"] = self.kind
        return snap


class PagedKVCache(_PagedPool):
    """The device arrays ``k``, ``v``: ``[L, NB, bs, H*Dh]`` (module
    doc).  ``state()`` hands the [k, v] list to
    ``Executor.run_callable``; ``update()`` swaps in the returned
    (donated-in-place) handles.

    ``dtype="int8"`` (the engine's ``cache_dtype``) stores blocks
    quantized in the SAME layout: k/v pools become int8 and two
    parallel f32 scale pools ``[L, NB, H]`` carry one abs-max scale per
    (block, head) — the qdq convention of ``kernels/quant.py``
    (``x ~= q * s / 127``).  ``state()`` then threads
    ``[k, v, k_scale, v_scale]`` so every dispatch moves the scale
    rows with the blocks (COW block copies copy the scale row through
    the same dim-1 block axis).  Everything host-side — the allocator,
    prefix cache, block tables — moves block IDS only and is unchanged.
    The f32 default keeps ``state()``, ``nbytes`` and the block layout
    byte-identical to the unquantized build."""

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 num_blocks: int, block_tokens: int,
                 dtype="float32"):
        super().__init__(num_layers, num_blocks, block_tokens, dtype)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.quantized = self.dtype == "int8"
        shape = (self.num_layers, self.num_blocks, self.block_tokens,
                 self.num_heads * self.head_dim)
        if self.quantized:
            self.k = jnp.zeros(shape, jnp.int8)
            self.v = jnp.zeros(shape, jnp.int8)
            sshape = (self.num_layers, self.num_blocks, self.num_heads)
            self.k_scale = jnp.zeros(sshape, jnp.float32)
            self.v_scale = jnp.zeros(sshape, jnp.float32)
        else:
            self.k = jnp.zeros(shape, dtype)
            self.v = jnp.zeros(shape, dtype)
            self.k_scale = None
            self.v_scale = None

    @property
    def nbytes(self) -> int:
        """ACTUAL pool bytes: dtype-aware block storage plus the scale
        pools when quantized — what the MemoryLedger pool and the
        per-tenant resident_kv_bytes attribute (a quantized cache must
        not report fp32-sized blocks)."""
        n = int(self.k.size) * self.k.dtype.itemsize * 2
        if self.k_scale is not None:
            n += int(self.k_scale.size) * self.k_scale.dtype.itemsize * 2
        return n

    def state(self) -> list:
        if self.quantized:
            return [self.k, self.v, self.k_scale, self.v_scale]
        return [self.k, self.v]

    def update(self, new_state: list) -> None:
        if self.quantized:
            self.k, self.v, self.k_scale, self.v_scale = new_state
        else:
            self.k, self.v = new_state

    def snapshot(self) -> dict:
        snap = super().snapshot()
        if self.quantized:
            # new keys only under the flag: the f32 snapshot surface
            # stays byte-identical
            snap["dtype"] = self.dtype
            snap["scale_bytes"] = int(
                self.k_scale.size) * self.k_scale.dtype.itemsize * 2
        return snap


class PagedLatentCache(_PagedPool):
    """The latent pool of a model with latent (MLA) attention: ONE array
    ``[L, NB, bs, W]`` and no V pool.  A token's row a layer is what its
    attention reads back — the compressed ``c`` after its norm and the shared
    rotary key after rotation — padded with zero lanes to a whole number of
    128-lane tiles (``kernels/mla.row_width``: 512 + 64 → 640), so that a
    block's ``[bs, W]`` fills whole tiles and the pool is updated in place
    like the K/V pools (class doc above).  Same manager otherwise: the same
    :class:`BlockAllocator`, block tables and ``snapshot()``, which adds
    ``kind: latent`` and the row's widths."""

    kind = "latent"

    def __init__(self, num_layers: int, rank: int, rope_dim: int,
                 row_width: int, num_blocks: int, block_tokens: int,
                 dtype="bfloat16"):
        if str(dtype) == "int8":
            raise ValueError("the latent pool has no int8 form: its rows "
                             "carry no per-block scale")
        super().__init__(num_layers, num_blocks, block_tokens, dtype)
        self.rank, self.rope_dim = int(rank), int(rope_dim)
        self.row_width = int(row_width)
        self.latent = jnp.zeros((self.num_layers, self.num_blocks,
                                 self.block_tokens, self.row_width), dtype)

    @property
    def nbytes(self) -> int:
        return int(self.latent.size) * self.latent.dtype.itemsize

    def state(self) -> list:
        return [self.latent]

    def update(self, new_state: list) -> None:
        (self.latent,) = new_state

    def snapshot(self) -> dict:
        snap = super().snapshot()
        snap.update(dtype=self.dtype, rank=self.rank, rope_dim=self.rope_dim,
                    row_width=self.row_width)
        return snap


class HybridStateCache(_PagedPool):
    """The per-stream state of a model that mixes state-space and attention
    layers — a paged pool and, under the same manager, the kinds of slot
    state the model says it HAS (``rings=``, ``recurrent=``, ``tails=``: a
    kind that is not given has no array), each with a layer count of its own:

    - ``kv`` ``[kv layers, NB, bs, row]``: the paged pool.  What a token's row
      holds is the model's to say: ``[k | v]`` of ``2·kw`` numbers, the K/V
      heads merged into the minor axis (``kv_width=kw``, the default), or any
      row of ``row_width`` lanes the model's attention reads back — a latent
      row ``[c | k_pe | 0]`` of a model with latent attention, 576 numbers in
      640 lanes, and no V pool (``row_width=640``: the rows of
      :class:`PagedLatentCache`, here beside slot rows and under ONE
      manager).  Whole lane tiles either way (class docs above).  Blocks,
      tables, the :class:`BlockAllocator` and the trash block are the paged
      pools'.  One layer where ONE full-attention layer's rows are what every
      attending layer reads (:mod:`~paddle_tpu.decode.sambay`: none copies
      it); every layer where every layer attends
      (:mod:`~paddle_tpu.decode.falcon_h1`).
    - ``rings=(window layers, W)`` → ``rings`` ``[window layers, slots ·
      W/rb, rb, 2·kw]``: a window layer keeps a slot's last ``W`` rows at
      ``position mod W``, as ``W/rb`` blocks of ``rb`` rows that belong to
      the slot for good — the bytes do not grow with a stream's context, and
      the paged decode kernel reads a ring as a table of the slot's own
      blocks.
    - ``recurrent=(state-space layers, state shape)`` → ``h`` ``[layers,
      slots, *state shape]`` float32: the recurrent state, one row a slot
      (Mamba-1: ``(N, Di)``, a decay a channel; Mamba-2: ``(heads, N, head
      channels)``; a gated delta rule: ``(heads, value channels, key
      channels)``, a decay a key channel).
    - ``tails=(convolution layers, K, width)`` → ``conv`` ``[layers, slots,
      K − 1, width]``: a causal convolution's last ``K − 1`` inputs, one row
      a slot.  A kind of its own: a model of short convolutions and no
      recurrence holds ``conv`` without ``h``.

    The last two are addressed by SLOT, not by block list: a prefill is told
    its slot and overwrites the slot's rows whole (that is the reset at a
    join); a decode step's row ``i`` is slot ``i``; a slot without a stream
    scribbles on its own rows only."""

    kind = "hybrid"
    RING_ROWS = 16

    def __init__(self, kv_width: int, num_blocks: int, block_tokens: int,
                 slots: int, dtype="bfloat16", kv_layers: int = 1, *,
                 rings: Optional[tuple] = None,
                 recurrent: Optional[tuple] = None,
                 tails: Optional[tuple] = None,
                 row_width: Optional[int] = None):
        if str(dtype) == "int8":
            raise ValueError("the hybrid state has no int8 form: its rows "
                             "carry no per-block scale")
        if row_width is not None and rings is not None:
            raise ValueError("a ring keeps [k | v] rows: a pool row of the "
                             "model's own width has none beside it")
        super().__init__(kv_layers, num_blocks, block_tokens, dtype)
        self.slots, self.window = int(slots), 0
        width = 2 * int(kv_width) if row_width is None else int(row_width)
        self.row_width = width
        self.kv = jnp.zeros((self.num_layers, self.num_blocks,
                             self.block_tokens, width), dtype)
        self.rings = self.h = self.conv = None
        if rings is not None:
            layers, window = (int(n) for n in rings)
            self.window = window
            self.ring_rows = min(window, self.RING_ROWS)
            if window % self.ring_rows:
                raise ValueError(f"a window of {window} is not whole blocks "
                                 f"of {self.ring_rows} rows")
            self.ring_blocks = window // self.ring_rows
            self.rings = jnp.zeros((layers, self.slots * self.ring_blocks,
                                    self.ring_rows, width), dtype)
        if recurrent is not None:
            layers, shape = recurrent
            self.h = jnp.zeros((int(layers), self.slots)
                               + tuple(int(n) for n in shape), jnp.float32)
        if tails is not None:
            layers, taps, conv_width = (int(n) for n in tails)
            self.conv = jnp.zeros((layers, self.slots, taps - 1, conv_width),
                                  dtype)
        self.live_tokens = 0        # the model's observer keeps it

    @staticmethod
    def _bytes(a) -> int:
        return 0 if a is None else int(a.size) * a.dtype.itemsize

    @property
    def kv_pool_bytes(self) -> int:
        return self._bytes(self.kv)

    @property
    def window_state_bytes(self) -> int:
        return self._bytes(self.rings)

    @property
    def recurrent_state_bytes(self) -> int:
        return self._bytes(self.h) + self._bytes(self.conv)

    @property
    def nbytes(self) -> int:
        return (self.kv_pool_bytes + self.window_state_bytes
                + self.recurrent_state_bytes)

    _KINDS = ("kv", "rings", "h", "conv")

    def state(self) -> list:
        """``[kv, rings, h, conv]`` less the kinds this model has none of:
        ``[kv, h, conv]`` without window layers, ``[kv, rings]`` without
        state-space layers, ``[kv, conv]`` with convolution tails alone."""
        return [a for a in (getattr(self, k) for k in self._KINDS)
                if a is not None]

    def update(self, new_state: list) -> None:
        held = [k for k in self._KINDS if getattr(self, k) is not None]
        if len(new_state) != len(held):
            raise ValueError(f"this cache holds {held}; got "
                             f"{len(new_state)} arrays")
        for k, a in zip(held, new_state):
            setattr(self, k, a)

    def snapshot(self) -> dict:
        snap = super().snapshot()
        snap.update(dtype=self.dtype, slots=self.slots, window=self.window,
                    kv_pool_bytes=self.kv_pool_bytes,
                    window_state_bytes=self.window_state_bytes,
                    recurrent_state_bytes=self.recurrent_state_bytes,
                    kv_live_tokens=int(self.live_tokens))
        if self.rings is None:
            del snap["window"], snap["window_state_bytes"]
        if self.h is None and self.conv is None:
            del snap["recurrent_state_bytes"]
        return snap
