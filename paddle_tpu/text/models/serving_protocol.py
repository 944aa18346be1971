"""What `inference.LLMEngine` asks of a model it serves.

The engine owns scheduling, pages and the compiled steps; the model owns
its arithmetic. Between them:

    model.config.vocab_size, .max_seq_len
    model.config.cache_kinds() -> [CacheKind, ...]
        the kinds of cache its layers keep. A PAGED kind: one page pool
        and one page table a slot: layers of a kind share page ids, so a
        page of that kind is one row of every one of their pools. A
        STATE kind (`CacheKind.slab`): no pages at all, one fixed slab
        a slot a layer, whatever the sequence's length (a recurrent
        layer's state). Paged kinds come first.
    model.compute_dtype()      -> the dtype activations and float pools
                                  default to
    model.step_counters        -> names of the int32 counters its step
                                  bodies return (may be empty)
    model._paged_decode_core(tok, pos, slot_ids, write_idx, page_tables,
        kv_lens, sample_idx, kv, kv_scales=None, ...)
        one ragged step over flat tokens (Tensors in and out): returns
        (logits [1, S, vocab], *new pools, *new scale planes[, counters
        [len(step_counters)]]). `kv` is the flat list k0, v0, k1, v1 …
        in LAYER order, each pool shaped by its layer's kind; a layer
        of a LATENT kind has ONE pool there, not two, and a layer of a
        STATE kind its slab's arrays `[num_slots, *shape]` in the
        slab's order. A slot's slab is the model's to carry: a step
        whose first row of a slot stands at position 0 starts that
        slot's state from ZERO whatever the slab holds (the engine
        admits a request at position 0 and replays a preempted one from
        there, so a slot never leaks a finished request's state), and
        every step writes back the state after its last row of the slot.
    model._paged_decode_fused(k, page_size, tok0, pos0, rem, fin0, eos,
        temps, top_ps, streams, page_tables, kv, kv_scales, key, ...)
        `k` such steps in one scan with sampling inside (raw arrays):
        returns (emits [k, S], new kv, new scales[, counters [k, C]]).

With ONE paged kind, `write_idx` is [T] and `page_tables` [S, MP]. With
several, both carry a leading axis in the order of `cache_kinds()`:
`write_idx` [kinds, T], `page_tables` [kinds, S, MP]. A state kind has
neither: its arrays are indexed by slot id.

The engine's counters of a state kind (`engine.stats`): `state_slabs_
live` (slabs, a slot a layer, that a running request holds now) and
`state_slabs_zeroed` (slabs an admission or a replay restarted from
zero); the span `llm_engine.reserve` carries `state_slabs`.
"""
import collections

__all__ = ["CacheKind"]


_LANES = 128


class CacheKind(collections.namedtuple(
        "CacheKind", "name layers kv_heads head_dim window head_major "
        "row_dim slab", defaults=(None, None))):
    """One kind of cache.

    name        what the engine's counters and spans call it
    layers      indices of the model's layers that keep this kind
    kv_heads    K/V heads a layer of this kind caches
    head_dim    their size
    window      None: a layer attends every earlier position and keeps
                every page. An int W: position p attends p - W + 1 … p,
                and the cache manager frees pages wholly behind that.
    head_major  pool layout: False [pages, page, kv_heads, head_dim],
                True [pages, kv_heads, page, head_dim]
    row_dim     None: a layer keeps TWO pools, keys and values a head.
                An int R: a LATENT kind. A layer keeps ONE pool [pages,
                page, R'], a row a token with no head axis, from which
                every head reads keys AND values (multi-head latent
                attention's `[c | k_rope]`); `kv_heads` and `head_dim`
                say nothing (None). R' is R in whole 128-lane tiles
                (`row_store`): the device stores a narrower last
                dimension at that width anyway, and a kernel can only
                copy whole tiles of it; the lanes past R hold zeros.
    slab        None: a PAGED kind (all of the above). A tuple of
                (shape, dtype) pairs: a STATE kind. A layer keeps one
                array `[num_slots, *shape]` a pair, a fixed slab a slot
                that does not grow with the sequence; every other field
                but `name` and `layers` says nothing (None). A dtype of
                None is the model's compute dtype. (A gated delta-rule
                layer: its float32 state `[heads, d_k, d_v]` and the
                last rows its short convolution still reads.)
    """
    __slots__ = ()

    @property
    def latent(self):
        return self.row_dim is not None

    @property
    def state(self):
        return self.slab is not None

    @property
    def pools_per_layer(self):
        if self.state:
            return len(self.slab)
        return 1 if self.latent else 2

    def slab_arrays(self, num_slots, compute_dtype):
        """[(shape, dtype)] of a state layer's arrays, in `kv` order."""
        return [((int(num_slots),) + tuple(shape), dtype or compute_dtype)
                for shape, dtype in self.slab]

    @property
    def row_store(self):
        return -(-self.row_dim // _LANES) * _LANES

    def pool_shape(self, num_pages, page_size, head_dim_store=None):
        if self.latent:
            return (num_pages, page_size, self.row_store)
        hd = self.head_dim if head_dim_store is None else head_dim_store
        if self.head_major:
            return (num_pages, self.kv_heads, page_size, hd)
        return (num_pages, page_size, self.kv_heads, hd)
