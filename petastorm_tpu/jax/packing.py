"""Sequence packing: fixed-shape batches from variable-length sequences.

XLA compiles one program per shape, so variable-length sequences must
become static shapes before they reach the chip.  Naive padding wastes
FLOPs quadratically (attention) on pad tokens; *packing* lays several
sequences end-to-end in one row of length ``max_len`` and tracks ownership
with ``segment_ids``, recovering most of the padding waste (the approach
of T5's pack_dataset and jax grain's pack-and-batch; no reference analog —
the closest reference machinery is host-side window assembly in
``petastorm/ngram.py :: NGram``, which emits per-window rows and leaves
batching shape problems to the consumer).

Host side (numpy, runs in the loader's worker pool or ``transform_fn``):

* :func:`pack_sequences` — pack a list of 1-D token arrays into
  ``(rows, max_len)`` with first-fit-decreasing (offline, best utilization).
* :func:`pack_stream` — streaming greedy packer: wraps any iterator of
  sequences (e.g. a reader column) and yields fixed-shape batches forever
  ready for ``device_put``.

Device side (jitted):

* :func:`segment_mask` — block-diagonal (optionally causal) attention mask
  from segment ids.
* :func:`packed_attention` — dense attention restricted to segments; same
  ``[batch, seq, heads, head_dim]`` convention as
  ``petastorm_tpu.ops.flash_attention`` and a drop-in ``attn_fn`` for
  ``models.transformer.TransformerLM`` via ``functools.partial``.
* :func:`next_token_targets` — LM targets + loss weights that never cross
  a packing boundary.

Packing invariant used throughout: segments within a row are CONTIGUOUS
(sequence i occupies one unbroken span), so "causal within segment" equals
"row-causal AND same segment" — a cheap mask, no per-segment position
bookkeeping on device.
"""

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ['pack_sequences', 'pack_stream', 'StreamPacker', 'segment_mask',
           'packed_attention', 'next_token_targets', 'document_starts',
           'document_ids']

#: What ``doc_ids`` holds where ``segment_ids`` is 0 (padding: no document).
NO_DOCUMENT = -1


def _emit(rows, max_len, dtype, pad_id, ids=None):
    """Render packed rows (lists of sequences) to the batch dict.

    ``dtype=None`` promotes over the actual sequences in this batch (the
    streaming packer can't know future dtypes, so each batch is exactly
    wide enough for its own rows — never a silent narrowing cast).
    ``ids`` (one list of document ids a row, beside ``rows``) adds the leaf
    ``doc_ids``: every token's document id, ``NO_DOCUMENT`` on padding.
    """
    n = len(rows)
    if dtype is None:
        dtype = np.result_type(*[s.dtype for seqs in rows for s in seqs])
    tokens = np.full((n, max_len), pad_id, dtype)
    segment_ids = np.zeros((n, max_len), np.int32)
    positions = np.zeros((n, max_len), np.int32)
    doc_ids = None
    if ids is not None:
        doc_ids = np.full((n, max_len), NO_DOCUMENT, np.result_type(
            np.int32, np.asarray([i for row in ids for i in row])))
    for r, seqs in enumerate(rows):
        off = 0
        for s, seq in enumerate(seqs):
            L = len(seq)
            tokens[r, off:off + L] = seq
            segment_ids[r, off:off + L] = s + 1
            positions[r, off:off + L] = np.arange(L)
            if doc_ids is not None:
                doc_ids[r, off:off + L] = ids[r][s]
            off += L
    batch = {'tokens': tokens, 'segment_ids': segment_ids,
             'positions': positions}
    if doc_ids is not None:
        batch['doc_ids'] = doc_ids
    return batch


def pack_sequences(sequences, max_len, pad_id=0):
    """Pack 1-D arrays into ``(rows, max_len)`` via first-fit-decreasing.

    Returns ``{'tokens', 'segment_ids', 'positions'}``; ``segment_ids`` is
    1-based per row (0 marks padding), ``positions`` restarts at 0 for each
    sequence.  Raises if any sequence exceeds ``max_len`` (truncation is a
    modeling decision — do it upstream where the tokenizer lives).
    """
    seqs = [np.asarray(s) for s in sequences]
    if not seqs:
        raise ValueError('no sequences to pack')
    for s in seqs:
        if s.ndim != 1:
            raise ValueError('expected 1-D sequences, got shape %r' % (s.shape,))
        if len(s) > max_len:
            raise ValueError('sequence of length %d exceeds max_len=%d; '
                             'truncate upstream' % (len(s), max_len))
    order = sorted(range(len(seqs)), key=lambda i: -len(seqs[i]))
    rows, room = [], []
    for i in order:
        L = len(seqs[i])
        for r in range(len(rows)):          # first fit
            if room[r] >= L:
                rows[r].append(seqs[i])
                room[r] -= L
                break
        else:
            rows.append([seqs[i]])
            room.append(max_len - L)
    return _emit(rows, max_len, np.result_type(*seqs), pad_id)


def pack_stream(seq_iter, max_len, rows_per_batch, pad_id=0,
                open_rows=32, drop_last=False):
    """Greedy streaming packer: yields fixed-shape batches from an iterator.

    Keeps up to ``open_rows`` partially-filled rows; each incoming sequence
    goes to the fullest row it fits in (best-fit — keeps rows closing
    fast), or opens a new row, and full-enough batches are emitted as soon
    as ``rows_per_batch`` rows have closed.  The tail is flushed as a final
    short-padded batch unless ``drop_last``.

    Suited to wrapping a reader column::

        seqs = (row.tokens for row in make_reader(url, ...))
        for batch in pack_stream(seqs, max_len=4096, rows_per_batch=8):
            step(batch['tokens'], batch['segment_ids'])

    The token dtype is STICKY: each batch is emitted in the promotion of
    every sequence dtype seen so far, so a stream mixing e.g. int32 and
    int64 widens once and stays wide instead of alternating batch dtypes
    (which would retrigger XLA compilation in a jitted step).
    """
    packer = StreamPacker(max_len, rows_per_batch, pad_id=pad_id,
                          open_rows=open_rows, drop_last=drop_last)
    for seq in seq_iter:
        for batch in packer.add(seq):
            yield batch
    for batch in packer.flush():
        yield batch


class StreamPacker(object):
    """The stateful engine under :func:`pack_stream`.

    ``add(seq)`` returns the batches that became ready; ``flush()`` drains
    the tail.  Exposed as a class (not just a generator) so loaders can
    snapshot the residue — open rows, closed rows, sticky dtype — for
    exact mid-epoch checkpoint/resume
    (``petastorm_tpu.jax.PackedDataLoader.state_dict``).

    ``add(seq, doc_id)`` carries the document's id through: every batch
    then holds ``doc_ids`` ``[rows, max_len]``, the id of the document each
    token belongs to (``NO_DOCUMENT`` on padding).  The layout is that of
    ``segment_ids``, so one leaf of a fixed shape says which documents a
    batch holds and where each lies, with no bound on documents a row that a
    ``[rows, max_docs]`` table would need (a row of ``max_len`` tokens can
    hold ``max_len`` documents) and no second leaf of counts; see
    :func:`document_ids`.  A packer is fed ids for every sequence or none.
    """

    def __init__(self, max_len, rows_per_batch, pad_id=0, open_rows=32,
                 drop_last=False):
        if rows_per_batch < 1 or open_rows < 1:
            raise ValueError('rows_per_batch and open_rows must be >= 1')
        self._max_len = max_len
        self._rows_per_batch = rows_per_batch
        self._pad_id = pad_id
        self._open_rows = open_rows
        self._drop_last = drop_last
        self._open = []      # list of [room, seqs, ids]
        self._closed = []    # list of (seqs, ids)
        self._dtype = None   # promoted over everything seen; never narrows
        self._with_ids = None

    @property
    def open_rows(self):
        """Rows that still take documents (at most ``open_rows``)."""
        return len(self._open)

    def _render(self, closed):
        seqs = [row[0] for row in closed]
        ids = [row[1] for row in closed] if self._with_ids else None
        return _emit(seqs, self._max_len, self._dtype, self._pad_id, ids)

    def _close_fullest(self):
        i = min(range(len(self._open)), key=lambda j: self._open[j][0])
        self._closed.append(tuple(self._open.pop(i)[1:]))

    def _ready_batches(self):
        out = []
        while len(self._closed) >= self._rows_per_batch:
            out.append(self._render(self._closed[:self._rows_per_batch]))
            self._closed = self._closed[self._rows_per_batch:]
        return out

    def add(self, seq, doc_id=None):
        """Fold one sequence in; returns the batches that became ready."""
        seq = np.asarray(seq)
        if seq.ndim != 1:
            raise ValueError('expected 1-D sequences, got %r' % (seq.shape,))
        if self._with_ids is None:
            self._with_ids = doc_id is not None
        elif self._with_ids != (doc_id is not None):
            raise ValueError('a packer carries an id for every sequence or '
                             'for none')
        self._dtype = (seq.dtype if self._dtype is None
                       else np.result_type(self._dtype, seq.dtype))
        max_len = self._max_len
        if len(seq) > max_len:
            raise ValueError('sequence of length %d exceeds max_len=%d'
                             % (len(seq), max_len))
        if len(seq) == max_len:     # exactly-full row: close it now
            self._closed.append(([seq], [doc_id]))
        else:
            fits = [i for i, row in enumerate(self._open)
                    if row[0] >= len(seq)]
            if fits:
                i = min(fits, key=lambda j: self._open[j][0])   # best fit
                row = self._open[i]
                row[0] -= len(seq)
                row[1].append(seq)
                row[2].append(doc_id)
                if row[0] == 0:
                    self._closed.append(tuple(self._open.pop(i)[1:]))
            else:
                self._open.append([max_len - len(seq), [seq], [doc_id]])
                if len(self._open) > self._open_rows:
                    self._close_fullest()
        return self._ready_batches()

    def close_open(self):
        """Close every open row as it is, fullest first; returns the batches
        that became ready.  What a loader calls at the end of an epoch, so
        that no document waits in an open row while the next epoch's are
        delivered (:class:`petastorm_tpu.jax.PackedDataLoader`)."""
        self._closed.extend(
            tuple(row[1:]) for row in sorted(self._open, key=lambda e: e[0]))
        self._open = []
        return self._ready_batches()

    def flush(self):
        """Drain open rows; returns the final batches (tail short-padded
        to full shape unless ``drop_last``)."""
        out = self.close_open()
        if self._closed and not self._drop_last:
            pad_rows = self._rows_per_batch - len(self._closed)
            batch = self._render(self._closed)
            if pad_rows:
                fill = {'tokens': self._pad_id, 'doc_ids': NO_DOCUMENT}
                batch = {k: np.concatenate(
                    [v, np.full((pad_rows,) + v.shape[1:], fill.get(k, 0),
                                v.dtype)])
                    for k, v in batch.items()}
            out.append(batch)
        self._closed = []
        return out

    # -- exact-checkpoint support --------------------------------------------

    def state_dict(self):
        state = {
            'open': [(room, [np.asarray(s) for s in seqs])
                     for room, seqs, _ in self._open],
            'closed': [[np.asarray(s) for s in seqs]
                       for seqs, _ in self._closed],
            'dtype': None if self._dtype is None else np.dtype(self._dtype).str,
        }
        if self._with_ids:
            state['open_ids'] = [list(ids) for _, _, ids in self._open]
            state['closed_ids'] = [list(ids) for _, ids in self._closed]
        return state

    def load_state_dict(self, state):
        self._with_ids = True if 'open_ids' in state else None
        open_ids = state.get('open_ids') or [
            [None] * len(seqs) for _, seqs in state['open']]
        closed_ids = state.get('closed_ids') or [
            [None] * len(seqs) for seqs in state['closed']]
        self._open = [[room, list(seqs), list(ids)]
                      for (room, seqs), ids in zip(state['open'], open_ids)]
        self._closed = [(list(seqs), list(ids))
                        for seqs, ids in zip(state['closed'], closed_ids)]
        self._dtype = (None if state['dtype'] is None
                       else np.dtype(state['dtype']))


def segment_mask(segment_ids_q, segment_ids_kv, causal=False):
    """Boolean attention mask ``[batch, 1, len_q, len_kv]`` from segment ids.

    A query may attend a key iff both carry the same NONZERO segment id;
    with ``causal=True`` additionally key_pos <= query_pos (valid because
    packed segments are contiguous — see module docstring).  The head axis
    is kept size-1 for broadcast.
    """
    q = jnp.asarray(segment_ids_q)
    kv = jnp.asarray(segment_ids_kv)
    mask = (q[:, :, None] == kv[:, None, :]) & (q[:, :, None] != 0)
    if causal:
        lq, lkv = q.shape[-1], kv.shape[-1]
        mask = mask & (jnp.arange(lkv)[None, :] <= jnp.arange(lq)[:, None])
    return mask[:, None, :, :]


def packed_attention(q, k, v, segment_ids, causal=True, scale=None):
    """Dense attention over packed rows: segments never attend each other.

    Same tensor convention as ``ops.flash_attention`` (``[batch, seq,
    heads, head_dim]``); softmax statistics in fp32.  Use as the
    ``attn_fn`` of ``models.transformer.TransformerLM``::

        attn = functools.partial(packed_attention, segment_ids=seg)
        TransformerLM(..., attn_fn=attn)

    O(seq^2) score memory — the correctness oracle and the moderate-length
    path; at long context use ``ops.flash_attention(..., segment_ids=seg)``
    — the same semantics as Pallas kernels with O(seq) memory.
    """
    if q.ndim != 4:
        raise ValueError('expected [batch, seq, heads, head_dim], got %r'
                         % (q.shape,))
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    mask = segment_mask(segment_ids, segment_ids, causal=causal)
    scores = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, -jnp.inf)
    # Fully-masked query rows (padding) would softmax over -inf -> NaN;
    # give them a finite row and zero them after.
    any_valid = mask.any(axis=-1, keepdims=True)
    scores = jnp.where(any_valid, scores, 0.0)
    weights = jax.nn.softmax(scores, axis=-1)
    weights = jnp.where(any_valid, weights, 0.0)
    out = jnp.einsum('bhqk,bkhd->bqhd', weights.astype(q.dtype), v)
    return out


def next_token_targets(tokens, segment_ids):
    """LM ``(targets, weights)`` that never cross a packing boundary.

    ``targets[t] = tokens[t+1]``; ``weights[t] = 1`` only where position
    ``t`` and ``t+1`` belong to the same nonzero segment (the last token of
    each sequence and all padding get weight 0).  Works on numpy or jax
    arrays; shapes ``[batch, seq]`` in, same out.
    """
    xp = jnp if isinstance(tokens, jnp.ndarray) else np
    targets = xp.concatenate(
        [tokens[:, 1:], xp.zeros_like(tokens[:, :1])], axis=1)
    seg_next = xp.concatenate(
        [segment_ids[:, 1:], xp.zeros_like(segment_ids[:, :1])], axis=1)
    weights = ((segment_ids == seg_next) & (segment_ids != 0)).astype(
        xp.float32)
    return targets, weights


def document_starts(segment_ids):
    """Boolean ``[batch, seq]``: True on the first token of every packed
    document (a nonzero segment id that differs from the one before it in
    the row).  Works on numpy or jax arrays."""
    xp = jnp if isinstance(segment_ids, jnp.ndarray) else np
    before = xp.concatenate(
        [xp.zeros_like(segment_ids[:, :1]), segment_ids[:, :-1]], axis=1)
    return (segment_ids != 0) & (segment_ids != before)


def document_ids(batch):
    """The ids of the documents a packed batch holds, row by row and in the
    order they lie in each row: ``doc_ids`` on the first token of every
    document.  Host side (the result's length depends on the data); on the
    device, ``doc_ids`` masked by :func:`document_starts` says the same in a
    fixed shape."""
    segment_ids = np.asarray(batch['segment_ids'])
    return np.asarray(batch['doc_ids'])[document_starts(segment_ids)]
