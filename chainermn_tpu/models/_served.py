"""The serving protocol, stated once: what ``serving.GenerationEngine``
asks of a model (:class:`ServedLM`), and the two cache helpers every
paged family needs.  ``serving/generate.py`` names no family; a family
is its mixers, its cache and what it overrides here."""

import dataclasses


class ServedLM:
    """What ``GenerationEngine`` calls on a model, and when.

    At construction, once:

    ``check_serving(paged=, int8_kv=, prefill_chunk=, prefix_sharing=,
    draft_model=, plan=)``
        the family refuses, in ONE message, every option it has no path
        for.  Here: the paged cache, greedy, and nothing else
        (``TransformerLM`` overrides it and refuses nothing).
    ``max_len``, ``vocab_size``, ``tp_axis``
        sizes; ``tp_axis`` is not None only for a model served over a
        plan.
    ``window_ring(page_size)``
        pages in a window layer's ring (its pages come out of a pool of
        their own, ``n_window_pages=``); 0 without window layers.
    ``has_state_row()``
        does a sequence hold a fixed-size state row beside its pages
        (``n_state_rows=``): one row for all recurrent layers.
    ``init_paged_kv_cache(n_pages, page_size, int8_kv=[,
    n_window_pages=][, n_state_rows=][, tp=])``
        the cache pytree, one array per layer and leaf, donated into
        every executable; the bracketed arguments only where the two
        members above (or a plan) call for them.  ``init_kv_cache(
        n_slots, max_len, int8_kv=)`` is the slot-addressed twin.
    ``paged_cache_bytes(cache_structs)``
        ``(bytes of one page, of one state row[, of one ring page])``
        over the layers that HOLD one: what the tick's cache-bytes
        attributes count in.  Asked only of a family with a state row
        or a ``page_counter``.
    ``page_counter``
        the name of the tick's page count for a family whose page is
        not K/V (``latent_pages_in_use``), else None.
    ``kv_lanes(cache_structs)``
        ``(live, stored)`` lanes of a pool row a decode call reads (the
        ``kv_live_lanes`` / ``kv_lanes`` attributes of
        ``serve_decode``); ``()`` sets neither.
    ``serve_compiler_options(platform)``
        the compiler's options for EVERY executable the engine builds
        for this model; ``{}`` for none.
    ``kv_cache_specs(cache, axis)``
        the cache's ``PartitionSpec`` tree under a plan.

    As traced bodies, one executable each and bucket:

    ``prefill_paged(params, cache, tokens, length, page_table, pos0)``,
    ``decode_step_paged(params, cache, tokens, positions, page_tables)``
    and the slot-addressed ``prefill`` / ``decode_step``
        each returns ``(logits, cache, counters)``, ``counters`` a
        tuple of float32 scalars named by ``serve_counters``; a table
        row is ``[full table | ring | state row]``.
    ``spec_verify``, ``spec_verify_paged``
        the target's pass over a draft's window.

    On a tick, while a recorder is on:

    ``decode_paged_grid(cache_structs, lengths, n_full, n_ring, tp=)``
        ``(pages read, grid steps)`` of one ``decode_step_paged`` over
        rows of those lengths, summed over layers, from shapes alone.

    A family sets ``family``, gives the members of the first group
    that raises below (``paged_cache_bytes`` only where it is asked),
    and overrides a default only where it differs.
    """

    #: the family's name, and the cache it is served through, in its
    #: refusals
    family = None
    cache_name = 'paged cache'
    #: why NO serving member of the family has a path, where none has
    unserved = None

    serve_counters = ()
    page_counter = None
    tp_axis = None

    @classmethod
    def from_config(cls, cfg, **overrides):
        """The model of a ``config.json``-shaped dict; keys this class
        does not know are left where they are, a list becomes the tuple
        a frozen dataclass can hash."""
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in cfg.items() if k in known}
        kw.update(overrides)
        return cls(**kw)

    @property
    def max_len(self):
        return self.max_position_embeddings

    def window_ring(self, page_size):
        return 0

    def has_state_row(self):
        return False

    def kv_lanes(self, cache):
        return ()

    def serve_compiler_options(self, platform):
        return {}

    def check_serving(self, **asked):
        """One refusal for every engine option a paged-only family has
        no path for; ``paged=True`` and greedy decoding is the path
        there is."""
        if self.unserved:
            self._not_yet('check_serving')
        wrong = [name for name, value in sorted(asked.items())
                 if name != 'paged' and value]
        if not asked.get('paged'):
            wrong.insert(0, 'paged=False')
        if wrong:
            raise ValueError(
                'a model of the %s family is served through the %s only '
                '(paged=True, prefix_sharing=False, no prefill_chunk, '
                'int8_kv, draft model or plan): asked for %s'
                % (self.family, self.cache_name, ', '.join(wrong)))

    def _not_yet(self, what):
        raise NotImplementedError('%s.%s: %s' % (
            type(self).__name__, what, self.unserved
            or 'not in this family yet (%s, one chip)' % self.cache_name))

    # -- what every served family gives --------------------------------
    def init_paged_kv_cache(self, *a, **kw):
        self._not_yet('init_paged_kv_cache')

    def prefill_paged(self, *a, **kw):
        self._not_yet('prefill_paged')

    def decode_step_paged(self, *a, **kw):
        self._not_yet('decode_step_paged')

    def decode_paged_grid(self, *a, **kw):
        self._not_yet('decode_paged_grid')

    def paged_cache_bytes(self, *a, **kw):
        self._not_yet('paged_cache_bytes')

    # -- what a paged-only family has no path for ----------------------
    def init_kv_cache(self, *a, **kw):
        self._not_yet('init_kv_cache (slot-addressed cache)')

    def prefill(self, *a, **kw):
        self._not_yet('prefill (slot-addressed cache)')

    def decode_step(self, *a, **kw):
        self._not_yet('decode_step (slot-addressed cache)')

    def spec_verify(self, *a, **kw):
        self._not_yet('spec_verify (speculative decoding)')

    def spec_verify_paged(self, *a, **kw):
        self._not_yet('spec_verify_paged (speculative decoding)')

    def kv_cache_specs(self, *a, **kw):
        self._not_yet('kv_cache_specs (tensor parallelism)')


def with_leaves(cache, at, **leaves):
    """``cache`` with the ``at``-th leaf of each named tuple replaced
    (each written once a call, so the donated buffer is updated where
    it lies)."""
    return dict(cache, **{
        name: cache[name][:at] + (leaf,) + cache[name][at + 1:]
        for name, leaf in leaves.items()})


def row_bytes(leaves):
    """Bytes of one row (a page, a state row) over ``leaves``, which
    may be their structs."""
    return sum(leaf.dtype.itemsize * leaf.size // leaf.shape[0]
               for leaf in leaves)
