"""A merging t-digest (Dunning & Ertl, "Computing Extremely Accurate
Quantiles Using t-Digests", arXiv:1902.04023) as a device aggregate.

Unlike every other sketch here, a t-digest's update is not one scatter
combinator: a digest is a function of its values in ARRIVAL ORDER.
Per slot it holds a buffer of raw values, up to ``centroids``
(mean, weight) pairs sorted by mean, the buffer's fill, the count and
the min and max.  The semantics, fixed so that a plain reference
(``tests/tdigest_reference.py``) reproduces them and no micro-batch,
flush or fire cut changes them:

- each value (float32) is appended to the buffer; a value that would
  be the (B+1)-th in it first triggers a COMPRESS, then goes in;
- a compress sorts the centroids and the buffer together by mean
  (ascending, stable; centroids first, then the buffer in arrival
  order) and makes one greedy pass: the next entry joins the current
  centroid while the weight up to and including it stays within
  n * k1^-1(k1(q0) + 1), q0 the current centroid's left edge and k1
  the scale function delta / (2 pi) * asin(2q - 1); otherwise it
  starts a new centroid.  The buffer empties;
- ``result`` compresses, then interpolates (``_interpolate``).

In integers the pass's test needs no transcendental: with W the weight
left of the current centroid, S its weight with the next entry, n the
digest's count, h = sin^2(pi / delta) and s = sin(2 pi / delta),
n * k1^-1(k1(W / n) + 1) = W - (2W - n) h + s sqrt(W (n - W)), and the
limit is n itself once n - W <= n h (k1(q0) + 1 past k1's top).  So
the entry joins while n - W <= n h or S <= s sqrt(W (n - W)) - (2W - n) h.
Weights are exact integers; means float32 (the chip has no fast
float64), each centroid's mean its weighted sum over its weight.

**At most delta centroids.**  After a compress, every two neighbours
together span more than 1 in k1 (the pass started the second because
the first could not take it), and k1 runs over delta / 2.  Disjoint
pairs therefore number fewer than delta / 2, so a digest holds at most
delta - 1 centroids, delta + 1 if float32 rounds a limit the wrong way
at every pair: ``centroids`` = 128 is that bound padded to the lanes.

On the device, ``update`` sorts a flush by slot (stable: a slot's rows
keep their order), which gives each row its rank in its slot.  From
the slot's fill a row either appends into the ``[slots, B]`` buffer
table in place (the TPU: a kernel that sets each value into the
(8 slots x 128 cells) tile that holds it) or belongs to a digest that
overflows inside this flush.  Those digests are gathered K at a time
and go through their rounds (fill from their rows, compress) in a
dense block, then written back as rows.  A compress sorts in XLA and
walks its pass with the digests along the lanes: on the TPU in a
kernel that holds 128 digests' entries and centroids in VMEM for the
whole pass, elsewhere as an XLA loop (``_pass``).  ``result`` groups
the fired digests by count into static length classes, so that a
digest of two values is not padded to B + C entries.
"""

from __future__ import annotations

import math
from fractions import Fraction
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.ops.device_agg import DeviceAggregateFunction, StateSpec
# a float32 table with a multiple of 128 columns is held slot-major in
# (8 x 128) tiles; the in-place append is the sketches' tile kernel
from flink_tpu.ops.sketches import (
    _LANES,
    _SUBLANES,
    _TILE_FORM_MAX_ROWS,
    _tile_form_runs,
    _tiles_in_place,
)

#: overflowing digests gathered into one dense block
OVERFLOW_BLOCK = 32
#: ``result``'s length classes: (largest count, digests a chunk).  A
#: digest of n values holds at most n entries (centroids + buffer), so
#: its class's count bounds its pass; the last class takes every count
RESULT_CLASSES = ((4, 16384), (16, 4096), (64, 1024), (256, 256),
                  (None, 128))


class TDigestAggregate(DeviceAggregateFunction):
    """Merging t-digest, scale function k1, compression ``delta``;
    ``result`` returns the requested quantiles per slot, [S, Q]."""

    needs_value = True
    value_dtype = np.dtype(np.float32)
    #: a merge of two digests is not the digest of their union in
    #: arrival order: an engine that composes windows from pane merges
    #: would answer something else than the pinned state route
    pane_merges_exact = False

    #: what the centroid means are held in
    mean_dtype = np.dtype(np.float32)

    def __init__(self, quantiles: Sequence[float] = (0.5, 0.99),
                 delta: int = 100, buffer: int = 512,
                 centroids: int = 128):
        if centroids < delta + 1:
            raise ValueError("centroids must hold delta + 1")
        self.quantiles = tuple(quantiles)
        self.delta = delta
        self.buffer = buffer
        self.centroids = centroids
        self.h = math.sin(math.pi / delta) ** 2
        self.s = math.sin(2 * math.pi / delta)
        # ceil(q * n) in whole numbers: q as the fraction it is written as
        self._q_fractions = tuple(Fraction(str(q)) for q in self.quantiles)
        for q in self._q_fractions:
            if not 0 <= q <= 1 or q.denominator >= _RANK_DENOMINATOR_LIMIT:
                raise ValueError(f"quantile {q} must lie in [0, 1] with a "
                                 f"denominator below 2^29")

    def state_specs(self) -> Dict[str, StateSpec]:
        return {"buf": StateSpec((self.buffer,), np.dtype(np.float32), 0),
                "mean": StateSpec((self.centroids,), self.mean_dtype, 0),
                "weight": StateSpec((self.centroids,), np.dtype(np.int32), 0),
                "fill": StateSpec((), np.dtype(np.int32), 0),
                "count": StateSpec((), np.dtype(np.int32), 0),
                "min": StateSpec((), np.dtype(np.float32), np.inf),
                "max": StateSpec((), np.dtype(np.float32), -np.inf)}

    # ---- the compress ------------------------------------------------
    def _compress(self, m, w, steps, c_out):
        """The greedy pass over entries (means ``m`` float32[G, L],
        weights ``w`` int32[G, L], weight 0 = padding) → (mean, weight)
        [G, c_out].  ``steps`` entries are walked; the caller knows that
        every real one lies among them once sorted."""
        with jax.named_scope("tdigest.compress"):
            inf = jnp.float32(np.inf)
            m = jnp.where(w > 0, m.astype(jnp.float32), inf)
            m, w = jax.lax.sort((m, w), dimension=1, is_stable=True,
                                num_keys=1)
            nf = jnp.sum(w, axis=1).astype(jnp.float32)
            # entries along the rows, digests along the lanes
            osum, ow = _pass(m[:, :steps].T, w[:, :steps].T, nf,
                             self.h, self.s, c_out)
            mean = jnp.where(ow > 0, osum / jnp.maximum(ow, 1)
                             .astype(jnp.float32), 0.0)
            return mean.astype(self.mean_dtype), ow

    def _entries(self, parts):
        """(means, weights) of [G, *] pieces, joined along the entries
        in the order given (the order a stable sort keeps for ties)."""
        return (jnp.concatenate([m.astype(jnp.float32) for m, _ in parts],
                                axis=1),
                jnp.concatenate([w for _, w in parts], axis=1))

    @staticmethod
    def _buffer_part(buf, fill):
        cells = jnp.arange(buf.shape[1], dtype=jnp.int32)[None, :]
        return buf, (cells < fill[:, None]).astype(jnp.int32)

    # ---- update ------------------------------------------------------
    #: `counted_update` counts digests compressed (one per compress of
    #: one digest) and rounds of compress dispatched (one per round of a
    #: block of overflowing digests); `counted_result` the entries
    #: (centroids and buffered values) the digests it finished held
    counters = ("tdigest_ingest_compresses", "tdigest_compress_rounds",
                "tdigest_fired_entries")

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        return self.counted_update(state, slots, values, vh_hi, vh_lo,
                                   mask)[0]

    def counted_update(self, state, slots, values, vh_hi, vh_lo, mask):
        """(the new state, int32[3] of `counters`)."""
        B = self.buffer
        cap = state["fill"].shape[0]
        n_rows = slots.shape[0]
        rows = jnp.arange(n_rows, dtype=jnp.int32)
        # a slot's rows in their order: a stable sort by slot, padding
        # (the table's size) last
        keyed = jnp.where(mask, slots.astype(jnp.int32), cap)
        order = jnp.argsort(keyed, stable=True)
        s, v = keyed[order], values[order].astype(jnp.float32)
        valid = s < cap
        first = jnp.concatenate([jnp.ones(1, bool), s[1:] != s[:-1]])
        last = jnp.concatenate([s[1:] != s[:-1], jnp.ones(1, bool)])
        run_start = jax.lax.cummax(jnp.where(first, rows, 0))
        run_end = jax.lax.cummin(jnp.where(last, rows, n_rows),
                                 reverse=True)
        rank = rows - run_start
        m = run_end - run_start + 1
        at = jnp.minimum(s, cap - 1)
        fill = state["fill"][at]
        count = state["count"][at]
        pos = fill + rank
        # compresses of the slot's digest inside this flush: before the
        # value at virtual position B, 2B, ...
        rounds = (fill + m - 1) // B
        head = valid & first
        head_slot = jnp.where(head, s, cap)
        out = dict(state)
        out["fill"] = state["fill"].at[head_slot].set(
            fill + m - rounds * B, mode="drop")
        out["count"] = state["count"].at[head_slot].set(count + m,
                                                        mode="drop")
        row_slot = jnp.where(valid, s, cap)
        out["min"] = state["min"].at[row_slot].min(v, mode="drop")
        out["max"] = state["max"].at[row_slot].max(v, mode="drop")
        # rows of digests that stay within their buffer: appended where
        # they lie
        direct = valid & (rounds == 0)
        with jax.named_scope("tdigest.append"):
            out["buf"] = _append(state["buf"], jnp.where(direct, s, cap),
                                 pos % B, v)
        # the digests that overflow: K at a time through a dense block
        over = head & (rounds >= 1)
        n_over = jnp.sum(over.astype(jnp.int32))
        k = min(OVERFLOW_BLOCK, n_rows)
        heads = jnp.concatenate([
            jnp.argsort(~over, stable=True).astype(jnp.int32),
            jnp.full(k, n_rows - 1, jnp.int32)])

        def block(i, st, dispatched):
            r = jax.lax.dynamic_slice(heads, (i * k,), (k,))
            live = i * k + jnp.arange(k) < n_over
            d_slot = jnp.where(live, s[r], cap)
            d_at = jnp.minimum(d_slot, cap - 1)
            d_fill, d_m = fill[r], m[r]
            d_rounds = jnp.where(live, rounds[r], 0)
            d_start = r
            buf = st["buf"][d_at]
            mean = st["mean"][d_at]
            weight = st["weight"][d_at]
            cells = jnp.arange(B, dtype=jnp.int32)[None, :]

            def take(buf, epoch):
                # cell c of a digest gets its row of rank epoch*B + c - fill
                rk = epoch[:, None] * B + cells - d_fill[:, None]
                ok = (rk >= 0) & (rk < d_m[:, None]) & live[:, None]
                got = v[jnp.clip(d_start[:, None] + rk, 0, n_rows - 1)]
                return jnp.where(ok, got, buf)

            def one_round(j, carry):
                buf, mean, weight = carry
                on = d_rounds >= j
                buf = take(buf, jnp.where(on, j - 1, -2))
                em, ew = self._entries(
                    [(mean, weight),
                     self._buffer_part(buf, jnp.where(on, B, 0))])
                new_mean, new_weight = self._compress(
                    em, ew, self.centroids + B, self.centroids)
                mean = jnp.where(on[:, None], new_mean, mean)
                weight = jnp.where(on[:, None], new_weight, weight)
                return buf, mean, weight

            most = jnp.max(d_rounds)
            buf, mean, weight = jax.lax.fori_loop(
                1, most + 1, one_round, (buf, mean, weight))
            buf = take(buf, d_rounds)
            return {**st,
                    "buf": st["buf"].at[d_slot].set(buf, mode="drop"),
                    "mean": st["mean"].at[d_slot].set(mean, mode="drop"),
                    "weight": st["weight"].at[d_slot].set(weight,
                                                          mode="drop")}, \
                dispatched + most

        out, dispatched = jax.lax.fori_loop(
            0, (n_over + k - 1) // k, lambda i, c: block(i, *c),
            (out, jnp.int32(0)))
        compressed = jnp.sum(jnp.where(over, rounds, 0))
        return out, jnp.stack([compressed, dispatched, 0]).astype(jnp.int32)

    def update_runs_in_place(self, rows: int, capacity: int) -> bool:
        return _tile_form_runs() and append_form(
            rows, capacity, self.buffer) == "tiles"

    # ---- result --------------------------------------------------------
    def _finish(self, state, slots, length):
        """Compress and interpolate the digests of ``slots``, none of
        which counts more than ``length`` values (None: any count)."""
        c = self.centroids if length is None else min(length,
                                                      self.centroids)
        b = self.buffer if length is None else min(length, self.buffer)
        fill = state["fill"][slots]
        em, ew = self._entries(
            [(state["mean"][slots, :c], state["weight"][slots, :c]),
             self._buffer_part(state["buf"][slots, :b], fill)])
        mean, weight = self._compress(em, ew, c + b, c)
        return (self._interpolate(mean.astype(jnp.float32), weight,
                                  state["count"][slots],
                                  state["min"][slots], state["max"][slots]),
                jnp.sum((ew > 0).astype(jnp.int32), axis=1))

    def _interpolate(self, mean, weight, n, lo, hi):
        """The written rule: of n values, the quantile q is asked at
        t = ceil(q n) - 1/2; the points are the min at 0, each centroid
        at the centre of its weight (left edge + weight / 2) and the max
        at n; between two neighbouring points the answer is linear.  A
        centroid of weight 1 thus answers its own rank exactly."""
        left = jnp.cumsum(weight, axis=1) - weight
        centre = left.astype(jnp.float32) + weight.astype(jnp.float32) / 2
        nf = n.astype(jnp.float32)[:, None]
        real = weight > 0
        pos = jnp.concatenate([jnp.zeros_like(nf),
                               jnp.where(real, centre, nf), nf], axis=1)
        val = jnp.concatenate([lo[:, None], jnp.where(real, mean, hi[:, None]),
                               hi[:, None]], axis=1)
        outs = []
        for q in self._q_fractions:
            rank = jnp.maximum(_ceil_mul_div(n, q.numerator, q.denominator),
                               1)
            t = rank.astype(jnp.float32)[:, None] - 0.5
            b = jnp.clip(jnp.sum((pos < t).astype(jnp.int32), axis=1,
                                 keepdims=True), 1, pos.shape[1] - 1)
            pa = jnp.take_along_axis(pos, b - 1, axis=1)
            pb = jnp.take_along_axis(pos, b, axis=1)
            va = jnp.take_along_axis(val, b - 1, axis=1)
            vb = jnp.take_along_axis(val, b, axis=1)
            est = va + (vb - va) * ((t - pa) / (pb - pa))
            outs.append(jnp.where(n[:, None] > 0, est, jnp.nan)[:, 0])
        return jnp.stack(outs, axis=-1)

    def result(self, state, slots):
        return self.counted_result(state, slots, slots.shape[0])[0]

    def counted_result(self, state, slots, rows):
        """(float32[S, Q] quantiles, int32[3] of `counters`, of the
        first `rows` slots)."""
        n = slots.shape[0]
        slots = slots.astype(jnp.int32)
        count = state["count"][slots]
        bounds = jnp.asarray([c for c, _ in RESULT_CLASSES[:-1]], jnp.int32)
        cls = jnp.sum((count[:, None] > bounds[None, :]).astype(jnp.int32),
                      axis=1)
        order = jnp.argsort(cls, stable=True).astype(jnp.int32)
        sizes = jnp.sum((cls[:, None] == jnp.arange(len(RESULT_CLASSES))
                         [None, :]).astype(jnp.int32), axis=0)
        offsets = jnp.cumsum(sizes) - sizes
        widest = max(min(g, n) for _, g in RESULT_CLASSES)
        order = jnp.concatenate([order, jnp.zeros(widest, jnp.int32)])
        out = (jnp.full((n, len(self.quantiles)), jnp.nan, jnp.float32),
               jnp.int32(0))
        for c, (length, chunk) in enumerate(RESULT_CLASSES):
            g = min(chunk, n)

            def body(i, out, c=c, length=length, g=g):
                k = i * g + jnp.arange(g, dtype=jnp.int32)
                at = jax.lax.dynamic_slice(order, (offsets[c] + i * g,), (g,))
                est, entries = self._finish(state, slots[at], length)
                real = k < sizes[c]
                return (out[0].at[jnp.where(real, at, n)].set(est,
                                                             mode="drop"),
                        out[1] + jnp.sum(jnp.where(real & (at < rows),
                                                   entries, 0)))

            out = jax.lax.fori_loop(0, (sizes[c] + g - 1) // g, body, out)
        return out[0], jnp.stack([0, 0, out[1]]).astype(jnp.int32)

    # ---- merge ---------------------------------------------------------
    def merge_slots(self, state, dst, src):
        """state[dst] = compress(centroids and buffers of both), with an
        empty buffer (device twin of AggregateFunction.merge)."""
        dst = dst.astype(jnp.int32)
        src = src.astype(jnp.int32)
        em, ew = self._entries(
            [(state["mean"][dst], state["weight"][dst]),
             (state["mean"][src], state["weight"][src]),
             self._buffer_part(state["buf"][dst], state["fill"][dst]),
             self._buffer_part(state["buf"][src], state["fill"][src])])
        mean, weight = self._compress(em, ew, em.shape[1], self.centroids)
        return {**state,
                "mean": state["mean"].at[dst].set(mean),
                "weight": state["weight"].at[dst].set(weight),
                "fill": state["fill"].at[dst].set(0),
                "count": state["count"].at[dst].set(
                    state["count"][dst] + state["count"][src]),
                "min": state["min"].at[dst].min(state["min"][src]),
                "max": state["max"].at[dst].max(state["max"][src])}


#: `_ceil_mul_div`'s long division holds three denominators in int32
_RANK_DENOMINATOR_LIMIT = 1 << 29


def _ceil_mul_div(n, num: int, den: int):
    """ceil(n * num / den) of int32 counts ``n`` >= 0, for whole numbers
    0 <= num <= den < 2^29, with no product past int32 (a p99.99 rank
    of 300,000 values is 2,999,700,000 / 10,000): n = a den + b gives
    a num + ceil(b num / den), the second by long division along the
    bits of ``num``."""
    a, b = n // den, n % den
    q = r = jnp.zeros_like(n)
    for bit in reversed(range(num.bit_length())):
        r = 2 * r + (b if (num >> bit) & 1 else 0)     # below 3 den
        c = r // den
        q, r = 2 * q + c, r - c * den
    return a * num + q + (r > 0).astype(n.dtype)


# ---------------------------------------------------------------------
# The compress's greedy pass over sorted entries, digests along the
# lanes: an XLA loop everywhere but the TPU, where a kernel keeps the
# entries and the centroids of 128 digests in VMEM for the whole pass
# (a loop step there is a few vector instructions, not a dispatch of
# its own).  Both walk the same steps in the same order.
# ---------------------------------------------------------------------

def _step(left, cw, ew, nf, h, s):
    """One entry of weight ``ew`` against the current centroid (weight
    ``cw``, ``left`` to its left) → (merge, start a new centroid)."""
    real = ew > 0
    wf = left.astype(jnp.float32)
    limit = s * jnp.sqrt(wf * (nf - wf)) - (2 * wf - nf) * h
    fits = ((nf - wf) <= nf * h) | ((cw + ew).astype(jnp.float32) <= limit)
    merge = real & (cw > 0) & fits
    return merge, real & ~merge


def _pass_loop(m_t, w_t, nf, h, s, c_out):
    """float32[L, G] means and int32[L, G] weights, sorted along L →
    (Σ weight x mean, Σ weight) of each centroid, [G, c_out]."""
    g = m_t.shape[1]
    column = jnp.arange(c_out, dtype=jnp.int32)[None, :]

    def step(i, carry):
        left, cw, j, osum, ow = carry
        ew, em = w_t[i], m_t[i]
        merge, start = _step(left, cw, ew, nf, h, s)
        left = left + jnp.where(start, cw, 0)
        cw = jnp.where(merge, cw + ew, jnp.where(start, ew, cw))
        j = j + start.astype(jnp.int32)
        hit = (column == j[:, None]) & (ew > 0)[:, None]
        osum = osum + jnp.where(
            hit, (ew.astype(jnp.float32) * em)[:, None], 0.0)
        ow = ow + jnp.where(hit, ew[:, None], 0)
        return left, cw, j, osum, ow

    zeros = jnp.zeros(g, jnp.int32)
    _, _, _, osum, ow = jax.lax.fori_loop(
        0, m_t.shape[0], step,
        (zeros, zeros, zeros - 1, jnp.zeros((g, c_out), jnp.float32),
         jnp.zeros((g, c_out), jnp.int32)))
    return osum, ow


def _pass_tpu(m_t, w_t, nf, h, s, c_out, interpret=False):
    """`_pass_loop` as a kernel: a grid step is 128 digests (the
    lanes), their entries [L, 128] and centroids [C, 128] in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    steps, g = m_t.shape
    gp = -(-g // _LANES) * _LANES
    cp = -(-c_out // _SUBLANES) * _SUBLANES
    lp = -(-steps // _SUBLANES) * _SUBLANES
    pad = ((0, lp - steps), (0, gp - g))
    m_t = jnp.pad(m_t, pad)
    w_t = jnp.pad(w_t, pad)
    nf = jnp.pad(nf, (0, gp - g))[None, :]

    # h and s stay Python floats: constants of the kernel, rounded to
    # float32 where they meet its vectors, as in the loop's

    def kernel(n_ref, m_ref, w_ref, osum_ref, ow_ref):
        nf = n_ref[...]
        osum_ref[...] = jnp.zeros(osum_ref.shape, jnp.float32)
        ow_ref[...] = jnp.zeros(ow_ref.shape, jnp.int32)
        row = jax.lax.broadcasted_iota(jnp.int32, (cp, _LANES), 0)

        def step(i, carry):
            left, cw, j = carry
            ew = w_ref[pl.ds(i, 1), :]
            em = m_ref[pl.ds(i, 1), :]
            merge, start = _step(left, cw, ew, nf, h, s)
            left = left + jnp.where(start, cw, 0)
            cw = jnp.where(merge, cw + ew, jnp.where(start, ew, cw))
            j = j + start.astype(jnp.int32)
            hit = (row == j) & (ew > 0)
            osum_ref[...] += jnp.where(hit, ew.astype(jnp.float32) * em, 0.0)
            ow_ref[...] += jnp.where(hit, ew, 0)
            return left, cw, j

        zeros = jnp.zeros((1, _LANES), jnp.int32)
        jax.lax.fori_loop(0, steps, step, (zeros, zeros, zeros - 1))

    def lanes(i):
        return 0, i

    osum, ow = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((cp, gp), jnp.float32),
                   jax.ShapeDtypeStruct((cp, gp), jnp.int32)),
        grid=(gp // _LANES,),
        in_specs=[pl.BlockSpec((1, _LANES), lanes),
                  pl.BlockSpec((lp, _LANES), lanes),
                  pl.BlockSpec((lp, _LANES), lanes)],
        out_specs=[pl.BlockSpec((cp, _LANES), lanes),
                   pl.BlockSpec((cp, _LANES), lanes)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="tdigest_compress_pass",
    )(nf, m_t, w_t)
    return osum[:c_out, :g].T, ow[:c_out, :g].T


def _pass(m_t, w_t, nf, h, s, c_out):
    """`_pass_loop` on every platform but the TPU, `_pass_tpu` there;
    ``h`` and ``s`` are the scale function's constants (floats)."""
    return jax.lax.platform_dependent(
        m_t, w_t, nf, tpu=lambda *a: _pass_tpu(*a, h, s, c_out),
        default=lambda *a: _pass_loop(*a, h, s, c_out))


# ---------------------------------------------------------------------
# The append: a value into cell (slot, cell) of the `[slots, B]` float32
# buffer table.  Off the TPU a scatter of cells; on it, a kernel that
# sets each value into the (8 slots x 128 cells) tile that holds it,
# the table left where it lies (XLA's TPU lowering of a cell scatter
# below 2^31 cells rewrites the whole table around it).
# ---------------------------------------------------------------------

def append_form(rows: int, capacity: int, width: int) -> str:
    """How a batch of `rows` values reaches `float32[capacity, width]`
    on a TPU: "tiles" where the table has whole (8 x 128) tiles and the
    batch's scalars fit the kernel, "cells" otherwise."""
    if rows < 2 or rows > _TILE_FORM_MAX_ROWS:
        return "cells"
    if capacity % _SUBLANES == 0 and width % _LANES == 0:
        return "tiles"
    return "cells"


def _append_cells(buf, slot, cell, value):
    # a slot past the table is a row that is not appended here
    return buf.at[slot, cell].set(value, mode="drop")


def _append_tiles_tpu(buf, slot, cell, value, interpret=False):
    """Each value into the (8 slots x 128 cells) tile that holds its
    cell, in place, by the tile kernel the HLL and quantile sketches
    share; a row whose slot lies past the table sets nothing."""
    from jax.experimental.pallas import tpu as pltpu

    def _set_bits(cur, hit, bits):
        # the tile's float32 cells as their bits, the hit one set
        return pltpu.bitcast(
            jnp.where(hit, bits, pltpu.bitcast(cur, jnp.int32)), jnp.float32)

    return _tiles_in_place(
        buf, slot, cell,
        jax.lax.bitcast_convert_type(value.astype(jnp.float32), jnp.int32),
        _SUBLANES, _set_bits, "tdigest_append_tiles", interpret, wide=True)


def _append(buf, slot, cell, value):
    if append_form(slot.shape[0], buf.shape[0], buf.shape[1]) == "cells":
        return _append_cells(buf, slot, cell, value)
    return jax.lax.platform_dependent(buf, slot, cell, value,
                                      tpu=_append_tiles_tpu,
                                      default=_append_cells)
