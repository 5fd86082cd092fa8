"""``DecodeEngine`` — per-slot continuous batching over the GPT decode model.

The offline decode stack (``models/gpt.py: generate``) runs one fixed batch
start-to-finish: a single long request holds the whole batch hostage while
finished rows idle. This engine keeps the same fixed-shape/pjit discipline
but makes the batch dimension a SLOT pool: every row of the KV cache is an
independent request at its own position (``GPTConfig.slot_decode`` — the
``cache_index`` variable is per-row), so requests stream in and out of rows
while the shapes never change.

Without a draft model, exactly two jitted programs exist, both
AOT-compiled at construction; with one (``draft_cfg``/``draft_params`` +
``spec_k`` — speculative decoding), exactly FOUR, never more:
``prefill``, ``decode/verify`` (ONE program — the (k+1)-wide verify step
IS spec decode; there is no separate single-token program), and the
draft twins ``draft_prefill`` / ``draft_all``. See the speculative
section below.

- ``prefill_into_slot(ops)`` — one fixed-width prompt chunk into one
  slot. The slot's rows are sliced out of the engine state into a
  batch-1 PLAIN cache (scalar ``cache_index``) and run through the
  ``chunked_prefill`` cache-continuing model that offline
  ``generate(prefill_chunk=...)`` already uses; the ragged last chunk is
  right-padded and masked via the model's ``prefill_len`` (pad K/V never
  survives in the cache, the index advances by the valid count only). On
  the last chunk the program also samples the request's FIRST token —
  mirroring ``generate``'s split-then-pick exactly, so engine output is
  bit-compatible with offline decode per request.
- ``decode_all()`` — one masked token step across ALL slots
  (``slot_decode`` model), with per-slot temperature/top-k/top-p/eos
  under a per-slot rng stream (split-then-pick, the batch-1 ``generate``
  stream per slot). The pick (:func:`_pick_rows`, shared with the other
  two programs that pick) does only the work the step's live slots ask
  for: :func:`dtf_tpu.models.gpt.filter_logits_dynamic` and its
  vocabulary sorts run only in a step where some live slot filters, the
  noise only where one samples; an all-greedy step is an arg-max.

**A call crosses to the device once each way** (PR 32). Beside params and
state a prefill call takes ONE host array (:data:`_PREFILL_HEAD`'s
scalars, the request's seed among them, then the chunk's tokens) and a
decode step none; nothing runs on the device outside the programs (the
PRNG key is made inside prefill) and the host blocks on nothing before a
dispatch. Everything the host reads of a call — tokens, done flags, the
step's telemetry scalars, a routed-expert model's counts — rides ONE int32
vector (:func:`_pack_out`) whose copy to the host starts at dispatch and
is awaited once (:meth:`DecodeEngine._read`); a chunk that is not a
request's last reads nothing. ``counters["host_operands"]`` /
``["device_reads"]`` count both and tests/test_serve.py fences them. A
decode step's dispatch and read are two calls (``decode_begin``, then
``decode(handle=)``), so a scheduler can dispatch the next step before it
reads this one (the run-ahead tick).

With ``prefix_pages > 0`` the engine additionally keeps a device **page
pool** and two more AOT programs, ``page_save``/``page_load`` (fixed-shape
BATCHED copies of a slot's page set to/from the pool, one dispatch per
admission — see :mod:`dtf_tpu.serve.pages` and
:func:`dtf_tpu.models.gpt.cache_load_pages`); the decode/prefill programs
are untouched, so
``trace_counts`` stays pinned at ``{prefill: 1, decode: 1}`` and the page
programs carry their own ``page_trace_counts`` fence.

**Speculative decoding** (``spec_k > 0``): each tick is ``draft_all``
(the small draft model proposes k greedy tokens per active slot, one
dispatch, its own slot cache) followed by ``decode/verify`` (the target
scores all k+1 positions in one masked pass — the model's slot-verify
branch — samples its OWN token per position through the row's rng
stream, and accepts the longest proposal prefix matching those samples:
``n_emit = 1 + |match|`` tokens per slot per tick, cache index rolled
back to the accepted boundary per row, rejected-tail KV left masked by
the validity bias). Token streams are IDENTICAL to non-speculative
decode (greedy and seeded sampling alike — the verifier's samples are
the stream; proposals only decide how many positions per dispatch are
worth keeping), pinned by tests/test_serve_spec.py. The draft's cache
stays in sync through host-mirrored ``(tok, index)`` operands that ride
the one readback decode performs anyway; the draft never touches the page
pool (its prefill always covers the full prompt). A draft failure falls back
to verify-with-null-proposals — plain decode — instead of erroring
requests.

Because all programs are compiled executables, steady state CANNOT
recompile — a shape change would be a loud call-site error, not a silent
retrace (``trace_counts`` exposes the per-program trace counters the fence
test pins).

**The state is donated** (PR 25): ``prefill``, ``decode``/``verify``, the
draft twins and ``page_load`` take the engine state as a donated argument
and return its successor, so the KV cache is updated IN PLACE — no program
copies or relayouts a whole cache leaf (the slot-decode write is a
position-mask select on the leaf as it lies, ``gpt._cache_put_rows``; the
described-v5e compile fence in tests/test_chip_compile.py pins both).
``page_save`` is left alone: it returns the pool, which several engines
may mount. What donation means for the host:

- a call CONSUMES ``self._state`` and rebinds it from the result in the
  same statement; nothing else keeps a state array across a call (take a
  host COPY, ``np.array``, if you need one — the old arrays are deleted);
- everything that can REFUSE a request raises BEFORE the dispatch —
  ``prefill_chunk_into``'s validation, the AOT executable's shape and
  sharding checks, the chaos injectors that wrap ``decode`` /
  ``prefill_chunk_into`` / ``draft_propose`` — so the scheduler's "fail the
  request, keep the replica" path still holds a live state;
- a failure INSIDE a dispatched program loses the state it was given. The
  next call says so with :class:`EngineStateLost` instead of JAX's "Array
  has been deleted". Such a replica cannot serve again: it is the Router's
  to retire, which is where decode-path exceptions already go.

Sharded serving: pass ``mesh`` and TP-sharded params — the cache lands
``P('data','model')`` (:func:`dtf_tpu.models.gpt.cache_shardings`: slots
over data shards, heads over TP shards) and the decode step runs under
GSPMD; the analysis registry's ``gpt_serve`` config fences the DECODE
graph's collectives (:func:`decode_step_view`) — the per-token hot path.
Known cost, not fenced: the sharded PREFILL dynamic-slices one slot out of
the data-sharded batch axis with a traced index, which GSPMD spells as a
resharding of the touched cache leaves per chunk — acceptable while
prefill is chunk-bounded and rare relative to decode steps, but a
per-shard slot-arithmetic shard_map is the upgrade path if sharded prefill
ever dominates (docs/SERVING.md).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import operator
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dtf_tpu.core import executor
from dtf_tpu.models import gpt
from dtf_tpu.telemetry.spans import NO_SPAN, trace_annotation

log = logging.getLogger("dtf_tpu")

PyTree = Any


class EngineStateLost(RuntimeError):
    """The engine's donated state did not come back: a program failed
    after it was dispatched, and the cache it was given went with it."""


#: engine state keys that are flat per-slot arrays (leading dim n_slots),
#: next to the "cache" collection. One registry so the state builder, the
#: abstract view and the programs cannot desynchronize.
_SLOT_ARRAYS = (
    ("tok", jnp.int32),     # last emitted token (next decode input)
    ("temp", jnp.float32),  # 0 = greedy, else sampling temperature
    ("top_k", jnp.int32),   # 0 = off
    ("top_p", jnp.float32),  # 1.0 = off
    ("eos", jnp.int32),     # -1 = no stop token
    ("pad", jnp.int32),     # token emitted after eos (offline parity)
    ("done", jnp.bool_),    # has emitted eos
    ("active", jnp.bool_),  # fully prefilled; a False row (empty slot or
                            # mid-prefill between interleaved chunks) rides
                            # the decode step untouched: no cache write, no
                            # index advance, no rng consumption
)


def _leaf_name(path) -> str:
    return getattr(path[-1], "key", str(path[-1]))


def _slice_slot_cache(cache: PyTree, slot) -> PyTree:
    """One slot's rows as a batch-1 PLAIN cache (scalar ``cache_index``)
    for the ``chunked_prefill`` model. Leaves are selected by key path —
    the same completeness contract as beam search's reorder
    (``gpt._BATCH_LED_CACHE_KEYS``): an unknown leaf fails loudly instead
    of silently riding the slot un-sliced."""
    def leaf(path, x):
        name = _leaf_name(path)
        if name in gpt._ROW_LED_CACHE_KEYS:
            return jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=0)
        if name == "cache_index":
            return jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=0)[0]
        raise ValueError(
            f"unknown cache leaf {name!r}: teach serve/engine.py how to "
            "slice it per slot (see gpt._ROW_LED_CACHE_KEYS)")

    return jax.tree_util.tree_map_with_path(leaf, cache)


def _write_slot_cache(cache: PyTree, row: PyTree, slot) -> PyTree:
    """Write a batch-1 plain cache back into slot ``slot``."""
    def leaf(path, x, r):
        name = _leaf_name(path)
        if name in gpt._ROW_LED_CACHE_KEYS:
            return jax.lax.dynamic_update_slice_in_dim(x, r, slot, axis=0)
        if name == "cache_index":
            return jax.lax.dynamic_update_slice_in_dim(
                x, r[None], slot, axis=0)
        raise ValueError(f"unknown cache leaf {name!r}")

    return jax.tree_util.tree_map_with_path(leaf, cache, row)


def _pick(sub, logits_v, temp, top_k=None, top_p=None):
    """One slot's token pick — ``generate``'s ``pick`` at batch-1 shapes
    ([1,V] through the filter, [0] out), so the sampled stream is
    bit-identical to an offline batch-1 ``generate`` with the same rng.
    Without ``top_k`` / ``top_p`` the filter is left out, as ``generate``
    leaves it out when both are off: the same token as with both gates
    off, which hand the filter's input back, less its sorts."""
    safe_t = jnp.where(temp > 0.0, temp, 1.0)
    filt = logits_v[None, :] / safe_t
    if top_k is not None:
        filt = gpt.filter_logits_dynamic(filt, top_k=top_k, top_p=top_p)
    sampled = jax.random.categorical(sub, filt, -1)[0]
    greedy = jnp.argmax(logits_v[None, :], -1)[0]
    return jnp.where(temp > 0.0, sampled, greedy).astype(jnp.int32)


#: what a call of :func:`_pick_rows` had to do, by the index it returns; the
#: engine counts decode steps under ``sampler_steps_<name>``
_SAMPLER_PATHS = ("greedy", "unfiltered", "filtered")


def _pick_rows(subs, logits, temp, top_k, top_p, live):
    """The token pick of every row of one program call: ``subs`` [N, 2],
    ``logits`` [N, V], ``temp`` / ``top_k`` / ``top_p`` / ``live`` [N] ->
    ``(tokens [N] int32, path)``. ``live`` marks the rows whose pick the
    program keeps.

    The work is chosen ONCE, for all rows, from what the live rows ask
    for, and only the chosen branch runs (a ``lax.cond`` inside a vmapped
    :func:`_pick` would become a select that runs both sides — call this
    outside any ``vmap``):

    0. no live row samples: the arg-max of the raw logits. No sort, no
       softmax, no noise.
    1. some live row samples, none filters: :func:`_pick` without its
       filter on every row — tempered categorical noise, arg-max for the
       greedy rows.
    2. some live row filters (``top_k`` > 0 or ``top_p`` < 1 at
       ``temp`` > 0): :func:`_pick` on every row — the vocabulary sorts of
       ``filter_logits_dynamic`` included.

    A live row's token is bit-identical on whichever path the call takes:
    2 is ``_pick`` itself, 1 is ``_pick`` less a filter that hands its
    input back when both gates are off, 0 is what ``_pick`` selects for
    ``temp`` == 0. A row that is not live gets a token of the path its
    neighbours chose, which its caller throws away."""
    samples = live & (temp > 0.0)
    filters = samples & ((top_k > 0) | (top_p < 1.0))
    path = (jnp.any(samples).astype(jnp.int32)
            + jnp.any(filters).astype(jnp.int32))

    def greedy():
        return jnp.argmax(logits, -1).astype(jnp.int32)

    def unfiltered():
        return jax.vmap(_pick)(subs, logits, temp)

    def filtered():
        return jax.vmap(_pick)(subs, logits, temp, top_k, top_p)

    return jax.lax.switch(path, (greedy, unfiltered, filtered)), path


#: What a decode step's packed vector (:func:`_pack_out`) carries behind its
#: tokens and flags: the sampler's path, the valid cache positions summed
#: over the active slots (what a step must read), and how many slots were
#: active. A model with routed experts appends per expert layer each of
#: :data:`_MOE_LAYER_STATS`.
_STEP_OUT_NAMES = ("sampler_path", "cache_positions", "active_slots")


#: what an expert layer sows into ``moe_stats`` (``DroplessMoE``), in the
#: order the decode program packs them: the experts that got a token, the
#: fullest expert's tokens, and of the experts the layer HOLDS the pairs
#: that landed on them and how many got a token
_MOE_LAYER_STATS = ("touched", "max_load", "held_pairs", "held_touched")


def _pack_out(tokens, done, *tail):
    """Everything the host reads of one program call as ONE int32 vector
    (``tokens``, ``done`` as 0/1, then ``tail``, each flattened): every
    array read back is a transfer of its own (~0.4 ms of a tick each on a
    v5e's host, PERF.md section 6, PR 31), and one vector's copy can be
    started where the program is dispatched. :func:`_split_out` is its
    inverse on the host."""
    return jnp.concatenate([jnp.ravel(x).astype(jnp.int32)
                            for x in (tokens, done, *tail)])


def _split_out(vec: np.ndarray, n: int):
    """A :func:`_pack_out` vector on the host: ``(tokens [n], done [n]
    bool, tail)``."""
    return vec[:n], vec[n:2 * n].astype(bool), vec[2 * n:]


def _layers_in_order(tree: dict) -> list:
    return [tree[name] for name in
            sorted(tree, key=lambda name: int(name.split("_")[-1]))]


def _build_decode_fn(model: gpt.GPT):
    """decode_all: one masked token step across all slots."""
    with_moe = model.cfg.experts is not None
    collections_out = ["cache"] + (["moe_stats"] if with_moe else [])

    def decode_fn(params, state):
        active = state["active"]
        logits, mut = model.apply(
            {"params": params, "cache": state["cache"]},
            state["tok"][:, None], deterministic=True,
            mutable=collections_out, decode_active=active)
        lg = logits[:, 0]                                    # [S, V] f32

        # one split per row whatever the pick does with its half: a
        # request's sampling stream does not depend on its neighbours
        keys = jax.vmap(jax.random.split)(state["rng"])      # [S, 2, 2]
        rng = keys[:, 0]
        # a done row emits pad and an inactive row nothing: neither asks
        # the sampler for anything
        nxt, path = _pick_rows(keys[:, 1], lg, state["temp"],
                               state["top_k"], state["top_p"],
                               active & ~state["done"])
        # offline eos semantics per slot: a done row keeps stepping but
        # emits pad; done flips AFTER the eos token itself is kept.
        nxt = jnp.where(state["done"], state["pad"], nxt)
        done = state["done"] | ((state["eos"] >= 0) & (nxt == state["eos"]))
        # inactive rows are spectators: their rng/token/done rows must
        # survive the step bit-for-bit (a mid-prefill slot's rng stream is
        # the request's sampling stream — advancing it here would break
        # the offline-parity contract).
        new_state = {
            **state, "cache": mut["cache"],
            "rng": jnp.where(active[:, None], rng, state["rng"]),
            "tok": jnp.where(active, nxt, state["tok"]),
            "done": jnp.where(active, done, state["done"]),
        }
        tail = [path,                                   # _STEP_OUT_NAMES
                jnp.sum(jnp.where(
                    active, gpt.cache_index_of(state["cache"]), 0)),
                jnp.sum(active, dtype=jnp.int32)]
        if with_moe:
            layers = _layers_in_order(mut["moe_stats"])
            tail += [layer["experts"][key][0] for key in _MOE_LAYER_STATS
                     for layer in layers]
        return new_state, _pack_out(nxt, done, *tail)

    return decode_fn


def _build_draft_fn(model: gpt.GPT, k: int):
    """draft_all: k GREEDY proposals per active slot in ONE dispatch — an
    unrolled loop of single-token ``slot_decode`` steps of the (small)
    draft model, writing the draft's own KV cache as it goes. Greedy on
    purpose: proposals are guesses the verifier prefix-matches against
    its own sampled stream, so they carry no rng and no sampling params —
    the draft's job is to be RIGHT often, not random. ``sync_index``
    (host-tracked by the engine) first rolls every active row's draft
    cache index to the verifier's accepted boundary, so rejected
    proposals from the last tick are forgotten the same way the
    verifier's are: by index assignment, never by clearing."""
    def draft_fn(params, state, tok, sync_index):
        active = state["active"]
        cache = gpt.cache_rollback(state["cache"], sync_index, active=active)
        cur = tok
        props = []
        # k+1 steps for k proposals: the LAST step ingests d_k itself
        # (output discarded), so on a clean sweep — where the verifier
        # advances k+1 positions (k matches + the bonus token) — the
        # draft cache has no hole at position idx+k. Without it, every
        # full acceptance would leave one permanently unwritten position
        # behind the rolled-forward index, quietly poisoning all later
        # proposals for that slot.
        for _ in range(k + 1):
            logits, mut = model.apply(
                {"params": params, "cache": cache}, cur[:, None],
                deterministic=True, mutable=["cache"], decode_active=active)
            cache = mut["cache"]
            cur = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
            if len(props) < k:
                props.append(cur)
        return {**state, "cache": cache}, jnp.stack(props, axis=1)

    return draft_fn


def _build_verify_fn(model: gpt.GPT, k: int):
    """decode/verify: ONE (k+1)-token masked step across all slots — the
    speculative replacement for :func:`_build_decode_fn`'s single-token
    program (a spec engine compiles this under the same ``decode`` trace
    fence; there is no separate plain-decode program).

    Inputs per row: the pending token plus the k draft proposals. The
    model's slot-verify branch scores every position against the row's
    own cache; the verifier then samples its OWN token at each position
    through the row's rng stream — exactly one ``jax.random.split`` per
    EMITTED token, the same chain sequential decode consumes, with the
    same eos→pad freezing per position. Acceptance is a per-row PREFIX
    MATCH of the proposals against those sampled tokens: ``n_emit = 1 +
    |matching prefix|`` (position j+1's logits are only valid when
    inputs 1..j matched the emitted stream, which the prefix rule
    guarantees; the +1 is the verifier's own token — the correction on a
    mismatch, the bonus on a clean sweep). The cache index rolls back to
    the accepted boundary per row (:func:`gpt.cache_rollback`); rng/tok/
    done select the ``n_emit``-th chain entry, so a spec engine's visible
    state after a tick is what ``n_emit`` sequential decode steps would
    have left. Correct for ARBITRARY proposals (worst case n_emit = 1,
    i.e. plain decode) — the draft-failure fallback rides that."""
    def verify_fn(params, state, proposals):
        active = state["active"]
        idx0 = gpt.cache_index_of(state["cache"])              # [S]
        inputs = jnp.concatenate([state["tok"][:, None], proposals], axis=1)
        logits, mut = model.apply(
            {"params": params, "cache": state["cache"]}, inputs,
            deterministic=True, mutable=["cache"], decode_active=active)

        def chain(key):
            # the row's rng chain, unrolled k+1 deep: entry j is what the
            # j-th sequential decode step would have split; it does not
            # depend on what was picked
            subs, keys, cur = [], [key], key
            for _ in range(k + 1):
                s2 = jax.random.split(cur)
                subs.append(s2[1])
                keys.append(s2[0])
                cur = s2[0]
            return jnp.stack(subs), jnp.stack(keys)

        subs, keys = jax.vmap(chain)(state["rng"])   # [S,k+1,2], [S,k+2,2]

        def per_pos(x):
            return jnp.repeat(x, k + 1)

        picks, path = _pick_rows(
            subs.reshape(-1, 2), logits.reshape(-1, logits.shape[-1]),
            per_pos(state["temp"]), per_pos(state["top_k"]),
            per_pos(state["top_p"]), per_pos(active & ~state["done"]))
        picks = picks.reshape(-1, k + 1)
        # the eos chain: what the j-th sequential step would have emitted
        toks, dones, done = [], [], state["done"]
        for j in range(k + 1):
            tkn = jnp.where(done, state["pad"], picks[:, j])
            done = done | ((state["eos"] >= 0) & (tkn == state["eos"]))
            toks.append(tkn)
            dones.append(done)
        toks, dones = jnp.stack(toks, axis=1), jnp.stack(dones, axis=1)
        match = jnp.cumprod((toks[:, :k] == proposals).astype(jnp.int32),
                            axis=1)
        n_emit = jnp.where(active, 1 + match.sum(axis=1),
                           0)                                   # [S] 0..k+1
        last = jnp.maximum(n_emit, 1) - 1
        new_tok = jnp.take_along_axis(toks, last[:, None], axis=1)[:, 0]
        new_done = jnp.take_along_axis(dones, last[:, None], axis=1)[:, 0]
        new_rng = jnp.take_along_axis(keys, n_emit[:, None, None],
                                      axis=1)[:, 0]
        cache = gpt.cache_rollback(mut["cache"], idx0 + n_emit,
                                   active=active)
        new_state = {
            **state, "cache": cache,
            "rng": jnp.where(active[:, None], new_rng, state["rng"]),
            "tok": jnp.where(active, new_tok, state["tok"]),
            "done": jnp.where(active, new_done, state["done"]),
        }
        return new_state, _pack_out(toks, dones, path, n_emit)

    return verify_fn


#: The scalars at the head of a prefill call's ONE host operand, an int32
#: vector; the chunk's ``prefill_chunk`` token columns follow them. One
#: array because every host array handed to a compiled program is a
#: transfer of its own (~0.2 ms each on a v5e's host, PERF.md section 6,
#: PR 32). ``temp`` and ``top_p`` travel as their float32 bit patterns, so
#: not a bit of a sampling parameter changes on the way; ``seed`` is the
#: int32 that ``jax.random.PRNGKey`` makes of a request's seed
#: (:func:`_seed_word`), and the program makes the key of it.
_PREFILL_HEAD = ("slot", "start", "n_valid", "reset", "is_last", "temp",
                 "top_k", "top_p", "eos", "pad", "seed")


def _seed_word(seed) -> np.int32:
    """``seed`` as ``jax.random.PRNGKey(seed)`` reads it without 64-bit
    types (the only mode this package runs in): wrapped to 32 bits, so
    -1 and 2**32 - 1 seed the same stream and 2**32 seeds 0's. Refuses
    what ``PRNGKey`` refuses (no integer: TypeError; beyond 64 bits:
    OverflowError) — on the host, before any dispatch."""
    return np.int64(operator.index(seed)).astype(np.int32)


def _f32_bits(x) -> np.int32:
    return np.float32(x).view(np.int32)


def _build_prefill_fn(model: gpt.GPT):
    """prefill_into_slot: one fixed-width chunk into one slot; on the last
    chunk, sample the request's first token (generate's split-then-pick).
    ``ops`` is the call's one host operand (:data:`_PREFILL_HEAD`, then
    the chunk). ``start`` is the number of already-valid leading positions
    (0 for a plain request; the prefix-page count × page size after page
    loads) — the reset lands the slot's index there, so the live chunks
    CONTINUE the loaded pages exactly like offline chunked prefill
    continues an advanced cache. Returns the state's successor and
    ``_pack_out(token, done)``."""
    def prefill_fn(params, state, ops):
        (slot, start, n_valid, reset, is_last, temp, top_k, top_p, eos, pad,
         seed) = (ops[i] for i in range(len(_PREFILL_HEAD)))
        chunk = ops[len(_PREFILL_HEAD):]
        reset, is_last = reset != 0, is_last != 0
        temp = jax.lax.bitcast_convert_type(temp, jnp.float32)
        top_p = jax.lax.bitcast_convert_type(top_p, jnp.float32)
        # made here, not on the host: an eager PRNGKey is a device program
        # and a blocking read that waits for whatever chunk is in flight
        key = jax.random.PRNGKey(seed)
        cache = state["cache"]
        row = _slice_slot_cache(cache, slot)
        # a fresh request starts at index `start` (0 without prefix pages;
        # stale slot contents past it need no clearing — validity is
        # derived from the index, gpt.py docstring)
        # ... but a recurrent state (conv layers) IS read whatever the
        # index says: a fresh request starts from zeros
        def admit(p, x):
            if _leaf_name(p) == "cache_index":
                return jnp.where(reset, jnp.asarray(start, x.dtype), x)
            if _leaf_name(p) in gpt._RECURRENT_CACHE_KEYS:
                return jnp.where(reset, jnp.zeros_like(x), x)
            return x

        row = jax.tree_util.tree_map_with_path(admit, row)
        logits, mut = model.apply(
            {"params": params, "cache": row}, chunk[None, :],
            deterministic=True, mutable=["cache"], prefill_len=n_valid)
        cache = _write_slot_cache(cache, mut["cache"], slot)
        # sampling-params rows are (re)stamped on every chunk of the
        # request — idempotent, and the slot is fully reinitialized by its
        # first chunk no matter who occupied it before.
        last = jax.lax.dynamic_index_in_dim(logits[0], n_valid - 1,
                                            axis=0, keepdims=False)  # [V]
        key_row = jnp.where(reset, key, state["rng"][slot])
        s2 = jax.random.split(key_row)
        # the pick is kept on the request's last chunk only
        picks, _ = _pick_rows(s2[1][None], last[None], temp[None],
                              top_k[None], top_p[None], is_last[None])
        tok_new = picks[0]
        done_new = is_last & (eos >= 0) & (tok_new == eos)
        new_state = {
            **state,
            "cache": cache,
            "rng": state["rng"].at[slot].set(
                jnp.where(is_last, s2[0], key_row)),
            "tok": state["tok"].at[slot].set(
                jnp.where(is_last, tok_new, state["tok"][slot])),
            "temp": state["temp"].at[slot].set(temp),
            "top_k": state["top_k"].at[slot].set(top_k),
            "top_p": state["top_p"].at[slot].set(top_p),
            "eos": state["eos"].at[slot].set(eos),
            "pad": state["pad"].at[slot].set(pad),
            "done": state["done"].at[slot].set(done_new),
            # the slot joins decode_all only once its LAST chunk landed;
            # until then it is a masked spectator of the all-slots step
            "active": state["active"].at[slot].set(is_last),
        }
        return new_state, _pack_out(tok_new, done_new)

    return prefill_fn


def _build_page_save_fn(n_pages: int):
    """page_save: scatter the NEW pages of one slot's prompt — page j in
    ``[lo, hi)`` lands at pool entry ``page_ids[j]`` — in one dispatch
    (a per-page program would pay as much host overhead as the prefill
    chunks the cache saves). Pages outside the window are pointed at the
    out-of-range sentinel, which drop-mode scatter discards."""
    def save_fn(state, pool, slot, page_ids, lo, hi):
        m = page_ids.shape[0]
        j = jnp.arange(m)
        ids = jnp.where((j >= lo) & (j < hi), page_ids, n_pages)
        return gpt.cache_save_pages(state["cache"], pool, slot, ids)

    return save_fn


def _build_page_load_fn():
    """page_load: gather a whole pinned page chain (``page_ids[:n_valid]``)
    into the leading positions of one slot — and DEACTIVATE the slot. The
    deactivate matters: a freshly admitted slot still carries its previous
    occupant's ``active``/index rows, and a decode_all running before the
    first live chunk would otherwise keep writing the old request's
    garbage K/V over the pages just landed."""
    def load_fn(state, pool, slot, page_ids, n_valid):
        return {
            **state,
            "cache": gpt.cache_load_pages(state["cache"], pool, slot,
                                          page_ids, n_valid),
            "active": state["active"].at[slot].set(False),
            "done": state["done"].at[slot].set(False),
        }

    return load_fn


def _state_struct(cfg: gpt.GPTConfig, n_slots: int,
                  mesh: Optional[Mesh]) -> PyTree:
    """Abstract engine state (ShapeDtypeStructs, shardings when mesh):
    the slot-batched cache collection plus the flat per-slot arrays."""
    model = gpt.GPT(cfg, mesh)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((n_slots, 1), jnp.int32)))
    cache = shapes["cache"]
    if mesh is not None:
        csh = gpt.cache_shardings(mesh, cache)
        cache = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh), cache, csh)
    rep = NamedSharding(mesh, P()) if mesh is not None else None

    def sds(shape, dtype):
        if rep is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    state = {"cache": cache,
             "rng": sds((n_slots, 2), jnp.uint32)}
    for name, dtype in _SLOT_ARRAYS:
        state[name] = sds((n_slots,), dtype)
    return state


def _zeros_like_struct(struct: PyTree) -> PyTree:
    def leaf(s):
        sh = getattr(s, "sharding", None)
        if sh is not None:
            # sharding-aware allocation: each device materializes only its
            # shard (the same move as generate()'s sharded cache0)
            return jnp.zeros(s.shape, s.dtype, device=sh)
        return jnp.zeros(s.shape, s.dtype)

    return jax.tree.map(leaf, struct)


def _cfg_label(cfg: gpt.GPTConfig) -> str:
    """A compact architecture identity for tune-cache keys — enough to
    distinguish model/draft pairs without serializing the whole config."""
    return (f"d{cfg.d_model}L{cfg.layers}h{cfg.heads}"
            f"kv{cfg.kv_heads_resolved}v{cfg.vocab_size}")


class DecodeEngine:
    """Slot-pooled online decode over a GPT checkpoint.

    ``cfg`` is the TRAINED architecture (decode fields are overridden
    here): ``max_len`` sizes the per-slot KV cache (prompt + generated
    tokens per request must fit), ``n_slots`` the concurrent-request pool,
    ``prefill_chunk`` the fixed width of the prefill program (>= 2 — a
    1-token apply would route to the decode branch). With ``mesh``, pass
    params already sharded (``shard_tree(params, mesh, gpt.tp_rules)``).
    """

    def __init__(self, cfg: gpt.GPTConfig, params: PyTree, *, n_slots: int,
                 max_len: int, prefill_chunk: int = 16,
                 mesh: Optional[Mesh] = None, kv_page_size: int = 0,
                 prefix_pages: int = 0, page_save_after: int = 2,
                 draft_cfg: Optional[gpt.GPTConfig] = None,
                 draft_params: PyTree = None, spec_k: int = 0,
                 shared_pages=None):
        if n_slots < 1:
            raise ValueError(f"n_slots={n_slots} must be >= 1")
        if max_len < 2:
            raise ValueError(f"max_len={max_len} must be >= 2 "
                             "(prompt + at least one generated token)")
        if prefill_chunk < 2:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be >= 2: a 1-token "
                "apply routes to the single-token decode branch, not the "
                "chunked-prefill path")
        if prefix_pages:
            if kv_page_size < 1:
                raise ValueError(
                    f"prefix_pages={prefix_pages} needs kv_page_size >= 1 "
                    f"(got {kv_page_size})")
            if max_len % kv_page_size:
                raise ValueError(
                    f"kv_page_size={kv_page_size} does not divide the "
                    f"cache length max_len={max_len}: a page window "
                    "crossing the cache end cannot be copied fixed-shape")
            if cfg.attn_window:
                raise ValueError(
                    f"the prefix page cache needs the plain slot=position "
                    f"cache layout; attn_window={cfg.attn_window} rolls "
                    "the buffer so page windows alias arbitrary positions")
        if cfg.has_recurrent_state:
            # a conv layer's state is a running summary, not positions: a
            # page of it cannot be loaded behind a prefix, a rejected
            # speculative tail cannot be rolled out of it, and the int8
            # cache's scales are per position (docs/SERVING.md)
            for asked, what in (
                    (prefix_pages, "the prefix page cache (prefix_pages)"),
                    (draft_cfg is not None or spec_k,
                     "speculative decoding (draft_cfg / spec_k)"),
                    (cfg.kv_cache_dtype == "int8",
                     "the int8 KV cache (kv_cache_dtype='int8')")):
                if asked:
                    raise ValueError(
                        f"{what} does not serve a model with conv layers: "
                        "their recurrent state has no positions to page, "
                        "roll back or rescale")
        if cfg.has_latent_cache:
            # a latent layer caches ONE row a position, [slots, width,
            # positions]: the page programs copy [slots, heads, positions,
            # width] windows, the verify step is not written for it, and
            # the config itself refuses an int8 form (docs/SERVING.md)
            for asked, what in (
                    (prefix_pages, "the prefix page cache (prefix_pages)"),
                    (draft_cfg is not None or spec_k,
                     "speculative decoding (draft_cfg / spec_k)")):
                if asked:
                    raise ValueError(
                        f"{what} does not serve a model with latent "
                        "attention yet: its cache leaf has no head axis to "
                        "page by, and no verify step to roll back")
        base = dataclasses.replace(cfg, decode_len=max_len,
                                   slot_decode=False, chunked_prefill=False)
        # the chunk may not be wider than ANY layer's cache: the rolling-
        # buffer write keeps only the last cache_len CHUNK positions, and
        # right-padding sits at the chunk's end — a wider chunk would push
        # valid prompt tokens out of the write window (their K/V silently
        # dropped, decode garbled with no shape error).
        min_cache = min(
            (min(max_len, w) if (w := base.layer_window(i)) else max_len)
            for i in range(base.layers))
        if prefill_chunk > min_cache:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} exceeds the smallest "
                f"per-layer cache length {min_cache} (max_len={max_len}, "
                f"attn_window={base.attn_window}); a right-padded chunk "
                "wider than the cache drops valid prompt K/V")
        self.cfg = base
        self.n_slots = n_slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.page_size = kv_page_size if prefix_pages else 0
        self.n_pages = prefix_pages
        self.mesh = mesh

        # ---- speculative decoding (draft model + verify step) -------------
        # spec_k == 0 with a draft present = "tuner decides" (the block-
        # shape sentinel contract, dtf_tpu/tune): the banked per-(model,
        # draft, slots) winner resolves the width; an explicit spec_k wins
        # with a warn-once when it overrides a MEASURED winner.
        if spec_k < 0:
            raise ValueError(f"spec_k={spec_k} must be >= 0")
        if spec_k and draft_cfg is None:
            raise ValueError(
                f"spec_k={spec_k} needs a draft model: pass draft_cfg + "
                "draft_params (speculation verifies a second model's "
                "proposals — there is nothing to verify without one)")
        self.spec_k = 0
        self.draft_cfg: Optional[gpt.GPTConfig] = None
        if draft_cfg is not None:
            if draft_params is None:
                raise ValueError("draft_cfg without draft_params")
            if base.attn_window or draft_cfg.attn_window:
                raise ValueError(
                    "speculative decoding needs the full windowless cache "
                    "layout on BOTH models (rolled buffers cannot roll a "
                    f"rejected tail back); got attn_window="
                    f"{base.attn_window}/{draft_cfg.attn_window}")
            if draft_cfg.vocab_size != base.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{base.vocab_size}: a draft must propose in the "
                    "verifier's token space")
            from dtf_tpu.tune import resolver as tune_resolver

            plan = tune_resolver.spec_k_plan(
                model=_cfg_label(base), draft=_cfg_label(draft_cfg),
                n_slots=n_slots,
                backend=jax.default_backend())
            if spec_k == 0:
                self.spec_k = plan.k
            else:
                self.spec_k = spec_k
                tune_resolver.note_override(
                    "spec_k", "k", spec_k, plan.k,
                    source=plan.source, measured=plan.measured)
            if self.spec_k + 1 >= max_len:
                raise ValueError(
                    f"spec_k={self.spec_k} leaves no room in the "
                    f"max_len={max_len} cache for a verify window")

        #: host-side call counters (plain ints — zero device readbacks):
        #: the bench/telemetry surface for "how much prefill work ran", and
        #: for what crossed to the device and back: ``host_operands`` are
        #: the host arrays handed to the engine's compiled programs beside
        #: params, state and pool (one a prefill chunk, none a decode
        #: step), ``device_reads`` the blocking device→host reads (one a
        #: decode step, one a request's last chunk).
        self.counters = {"prefill_chunks": 0, "decode_steps": 0,
                         "pages_loaded": 0, "pages_saved": 0,
                         "prefix_hit_tokens": 0, "prefix_miss_tokens": 0,
                         "probe_decodes": 0, "param_swaps": 0,
                         "host_operands": 0, "device_reads": 0}
        #: what the last engine call's routing did (models with routed
        #: experts; None for every other model and once read): numbers by
        #: name, host values from the call's one readback or from its own
        #: operands. The scheduler drains them into its
        #: SpanRecorder as ``serve_moe_<name>`` (docs/OBSERVABILITY.md
        #: section 7) and ``counters`` keeps their sums.
        self.moe_samples: Optional[dict] = None
        #: what the last decode step's readback carried behind its tokens
        #: (host ints, in the order of ``_STEP_OUT_NAMES``: the sampler's
        #: path as an index into ``_SAMPLER_PATHS``, the live cache
        #: positions, the active slots), until :meth:`take_samples` files
        #: and counts them; they rode the tokens' transfer, so a traced run
        #: transfers exactly what a measured run does.
        self._step_out = None
        #: the packed output of the last prefill chunk dispatched, until
        #: :meth:`prefill_wait` waits for it
        self._chunk_out = None
        self.counters.update(
            {f"sampler_steps_{name}": 0 for name in _SAMPLER_PATHS})
        if base.experts is not None:
            self.counters.update({"moe_decode_picks": 0,
                                  "moe_prefill_picks": 0,
                                  "moe_experts_touched": 0,
                                  "moe_max_expert_load": 0})
        #: the param VERSION this engine serves (ISSUE 14 hot-swap):
        #: monotone, bumped by :meth:`swap_params`, stamped into every
        #: completed record by the scheduler and used as the prefix-page
        #: EPOCH so a cached stem can never serve stale-weight KV. 0 is
        #: "as constructed"; launchers serving a published version stamp
        #: it via :meth:`set_param_version` before traffic.
        self.param_version = 0
        if self.spec_k:
            # acceptance/fallback accounting: proposed counts k per LIVE
            # verified row per tick, accepted counts the matched prefix
            # (n_emit - 1); stale still-active rows ride both sides, so
            # the scheduler's per-running-slot rollup is the exact one.
            self.counters.update({"draft_steps": 0,
                                  "draft_prefill_chunks": 0,
                                  "draft_fallbacks": 0,
                                  "spec_proposed": 0, "spec_accepted": 0})
        #: when True, each prefill-chunk and decode call is wrapped in a
        #: jax.profiler.TraceAnnotation (``dtf.serve.prefill_chunk`` /
        #: ``dtf.serve.decode``) carrying the request trace id(s) the
        #: scheduler threaded down — a ProfilerHook window over a serving
        #: run then shows WHICH requests each call served, joinable to
        #: the per-request chrome trace — and, nested in it, its two
        #: phases: ``dtf.engine.<call>.dispatch`` (operands built, the
        #: compiled program called, futures back) and ``.readback`` (the
        #: host waits for the outputs). Off by default: a TraceMe outside
        #: any profiling session is cheap but not free, and the id
        #: strings allocate per decode step.
        self.annotate_traces = False
        if mesh is None:
            # a restored checkpoint carries the TRAINING mesh's shardings;
            # unsharded serving runs on one device, and the AOT-compiled
            # programs (unlike plain jit) reject mismatched input shardings
            # instead of re-lowering — commit params here once.
            dev = jax.devices()[0]
            params = jax.tree.map(lambda x: jax.device_put(x, dev), params)
            if self.spec_k:
                draft_params = jax.tree.map(
                    lambda x: jax.device_put(x, dev), draft_params)
        self._params = params

        struct = _state_struct(dataclasses.replace(base, slot_decode=True),
                               n_slots, mesh)
        self._state = _zeros_like_struct(struct)
        # engine defaults that zeros get wrong: nucleus off, no stop token
        self._state["top_p"] = self._state["top_p"] + 1.0
        self._state["eos"] = self._state["eos"] - 1
        if mesh is not None:
            rep = NamedSharding(mesh, P())
            self._state["top_p"] = jax.device_put(self._state["top_p"], rep)
            self._state["eos"] = jax.device_put(self._state["eos"], rep)

        #: traces per program — the recompile fence. AOT compilation below
        #: traces each exactly once; any later increment would mean a
        #: shape-driven retrace, which the compiled executables make
        #: impossible by construction (they reject new shapes instead).
        #: With a draft model there are exactly FOUR programs — prefill,
        #: decode/verify (ONE program: the verify step IS spec decode),
        #: draft_prefill, draft — and the fence pins all four.
        self.trace_counts = {"prefill": 0, "decode": 0}

        def abs_of(tree):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if mesh is not None else None),
                tree)

        abs_params = abs_of(params)
        abs_state = abs_of(self._state)
        abs_trees = {"params": abs_params, "state": abs_state}
        if self.spec_k:
            self.trace_counts.update({"draft_prefill": 0, "draft": 0})
            dbase = dataclasses.replace(
                draft_cfg, decode_len=max_len, slot_decode=False,
                chunked_prefill=False)
            self.draft_cfg = dbase
            self._draft_params = draft_params
            dstruct = _state_struct(
                dataclasses.replace(dbase, slot_decode=True), n_slots, mesh)
            self._draft_state = _zeros_like_struct(dstruct)
            abs_trees["draft_params"] = abs_of(draft_params)
            abs_trees["draft_state"] = abs_of(self._draft_state)
        #: the serve program table: every program born fenced through
        #: dtf_tpu/core/executor.py — the SAME construction the analysis
        #: step views enumerate, with this engine's abstract trees (real
        #: array shardings: restored checkpoints keep their layouts).
        self.programs, models = program_table(
            base, n_slots=n_slots, max_len=max_len, mesh=mesh,
            prefill_chunk=prefill_chunk, spec_k=self.spec_k,
            draft_cfg=self.draft_cfg, counts=self.trace_counts,
            abs_trees=abs_trees)
        self._decode_model = models["decode"]
        self._prefill_model = models["prefill"]
        self._decode_c = self.programs["decode"].aot()
        self._prefill_c = self.programs["prefill"].aot()
        if self.spec_k:
            self._draft_decode_model = models["draft"]
            self._draft_prefill_model = models["draft_prefill"]
            self._draft_prefill_c = self.programs["draft_prefill"].aot()
            self._draft_c = self.programs["draft"].aot()
            #: host mirrors of the verifier's per-slot position and
            #: pending token (fed to draft_all as sync operands): updated
            #: from values decode() reads back ANYWAY (tokens/n_emit), so
            #: speculation adds zero extra device readbacks per tick.
            self._spec_tok = np.zeros((n_slots,), np.int32)
            self._spec_index = np.zeros((n_slots,), np.int32)
            self._draft_chunks = np.zeros((n_slots,), np.int32)
            #: SELF-speculation (draft ≡ target architecture): the draft
            #: cache is struct-identical to the target's, so the page
            #: programs accept it and a prefix-page hit shortcuts the
            #: DRAFT prefill too (same weights ⇒ the pooled KV is the
            #: draft's KV). With a distinct draft model the pool holds
            #: foreign KV and the draft always prefills the full prompt.
            self._draft_self = dbase == base
            self._draft_start = np.zeros((n_slots,), np.int32)
            self._draft_pending = np.zeros((n_slots,), np.int32)
            if self._draft_self:
                self.counters["draft_pages_loaded"] = 0

        #: the prefix page cache (None unless prefix_pages > 0): device
        #: pool + host index + two more AOT programs with their own trace
        #: fence — trace_counts itself stays pinned at {prefill, decode}.
        #: ``shared_pages`` mounts another engine's :class:`PageStore`
        #: instead of allocating — the disaggregation KV transport: pages a
        #: prefill replica saves are immediately loadable by every decode
        #: replica mounting the same store.
        self._page_store = None
        self.page_trace_counts = {}
        if shared_pages is not None and not prefix_pages:
            raise ValueError(
                "shared_pages needs prefix_pages > 0 on the mounting "
                "engine too (the pool shapes come from its own config)")
        if prefix_pages:
            from dtf_tpu.serve import pages as pages_lib

            pool_abs = pages_lib.pool_abstract(
                abs_state["cache"], prefix_pages, kv_page_size, mesh)
            if shared_pages is not None:
                pages_lib.check_pool_compatible(shared_pages.pool, pool_abs)
                if (shared_pages.index.n_pages != prefix_pages
                        or shared_pages.index.page_size != kv_page_size):
                    raise ValueError(
                        f"shared page store is {shared_pages.index.n_pages}"
                        f"x{shared_pages.index.page_size}-token pages; "
                        f"this engine asked for {prefix_pages}"
                        f"x{kv_page_size}")
                self._page_store = shared_pages
                self._owns_pages = False
            else:
                self._page_store = pages_lib.PageStore(
                    _zeros_like_struct(pool_abs),
                    pages_lib.PrefixIndex(prefix_pages, kv_page_size,
                                          save_after=page_save_after))
                self._owns_pages = True
            self.page_trace_counts = {"save": 0, "load": 0}
            page_programs = page_program_table(
                abs_state, pool_abs, n_pages=prefix_pages,
                max_len=max_len, kv_page_size=kv_page_size, mesh=mesh,
                counts=self.page_trace_counts)
            self.programs.update(page_programs)
            self._page_save_c = page_programs["save"].aot()
            self._page_load_c = page_programs["load"].aot()

    # ------------------------------------------------------------- host API

    @property
    def page_store(self):
        """The engine's mountable prefix-page state (None with the cache
        off) — pass as ``shared_pages=`` to further engines to share one
        pool+index (the disaggregation KV transport)."""
        return self._page_store

    @property
    def _prefix(self):
        return None if self._page_store is None else self._page_store.index

    @property
    def _pages(self):
        return self._page_store.pool

    @_pages.setter
    def _pages(self, pool):
        self._page_store.pool = pool

    @staticmethod
    def _live(state: PyTree) -> PyTree:
        """``state``, if the last program that was given it returned: the
        programs donate their state, so a call that raised after its
        dispatch left ``self._state`` naming deleted arrays (module
        docstring). One host-side flag test, no device work."""
        if state["tok"].is_deleted():
            raise EngineStateLost(
                "the engine state was donated to a program that failed "
                "after dispatch, so its KV cache is gone: this engine "
                "cannot serve again — retire the replica (the Router "
                "quarantines it) and build a new engine")
        return state

    def n_chunks(self, prompt_len: int) -> int:
        return math.ceil(prompt_len / self.prefill_chunk)

    def _annotation(self, name: str, **ids):
        """A jax.profiler.TraceAnnotation under ``annotate_traces``: one
        phase of an engine call (``dtf.engine.*``: dispatch until the
        program returns futures, readback of its outputs) or the whole
        call (``dtf.serve.*``, stamping the request trace ids) in the
        XPlane timeline; otherwise the one shared null context — nothing
        is constructed. Host-side marker only — never reads a device
        value."""
        if not self.annotate_traces:
            return NO_SPAN
        return trace_annotation(name, **ids)

    def _chunk_operand(self, slot: int, prompt: Sequence[int], chunk_i: int,
                       start: int, temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, eos: int = -1, pad: int = 0,
                       seed: int = 0) -> np.ndarray:
        """The one host operand of a prefill call (:data:`_PREFILL_HEAD`,
        then chunk ``chunk_i`` of ``prompt[start:]`` right-padded with
        zeros). Touches the chunk's own tokens only: the host's work a
        chunk is proportional to the chunk, not to the prompt."""
        c, head = self.prefill_chunk, len(_PREFILL_HEAD)
        lo = start + chunk_i * c
        seg = prompt[lo:lo + c]
        ops = np.zeros((head + c,), np.int32)
        ops[:head] = (
            slot, start, len(seg), chunk_i == 0,
            chunk_i == self.n_chunks(len(prompt) - start) - 1,
            _f32_bits(temperature), top_k, _f32_bits(top_p), eos, pad,
            _seed_word(seed))
        ops[head:head + len(seg)] = seg
        self.counters["host_operands"] += 1
        return ops

    def _read(self, out, n: int):
        """THE blocking read of a program call: its packed vector
        (:func:`_pack_out`, copy begun at dispatch) as ``(tokens [n],
        done [n], tail)`` on the host."""
        self.counters["device_reads"] += 1
        return _split_out(np.asarray(out), n)

    def prefill_chunk_into(self, slot: int, prompt: Sequence[int],
                           chunk_i: int, *, start: int = 0,
                           temperature: float = 0.0,
                           top_k: int = 0, top_p: float = 1.0,
                           eos_id: Optional[int] = None, pad_id: int = 0,
                           seed: int = 0,
                           trace_id: Optional[int] = None
                           ) -> Optional[tuple[int, bool]]:
        """Run prompt chunk ``chunk_i`` of a request into ``slot`` — the
        scheduler's prefill/decode interleave granularity (decode_all may
        run between chunks; the slot stays a masked spectator until its
        last chunk lands). ``start`` leading tokens are taken as already
        in the slot's cache (prefix pages loaded via
        :meth:`load_prefix_page`) — chunks cover ``prompt[start:]`` only.
        Returns ``(first_token, done)`` on the last chunk, None before."""
        if not 1 <= len(prompt) <= self.max_len - 1:
            raise ValueError(
                f"prompt length {len(prompt)} must be in [1, "
                f"{self.max_len - 1}] (max_len={self.max_len} covers "
                "prompt + generated tokens)")
        if not 0 <= start < len(prompt):
            raise ValueError(
                f"start={start} must be in [0, {len(prompt)}) — at least "
                "one prompt token must prefill live (the request's first "
                "sampled token comes from the last position's logits)")
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        n = self.n_chunks(len(prompt) - start)
        if not 0 <= chunk_i < n:
            raise ValueError(f"chunk {chunk_i} out of range [0, {n})")
        last = chunk_i == n - 1
        with self._annotation("dtf.serve.prefill_chunk", slot=slot,
                              chunk=chunk_i,
                              trace_id=-1 if trace_id is None else trace_id):
            with self._annotation("dtf.engine.prefill.dispatch"):
                ops = self._chunk_operand(
                    slot, prompt, chunk_i, start, temperature, top_k, top_p,
                    -1 if eos_id is None else eos_id, pad_id, seed)
                self._state, out = self._prefill_c(
                    self._params, self._live(self._state), ops)
                self._chunk_out = out
                if last:
                    out.copy_to_host_async()
            self.counters["prefill_chunks"] += 1
            if self.cfg.experts is not None:
                # pad columns choose no expert: the chunk's valid tokens
                picks = (int(ops[_PREFILL_HEAD.index("n_valid")])
                         * self.cfg.experts.top_k)
                self.counters["moe_prefill_picks"] += picks
                self.moe_samples = {"prefill_picks": picks}
            if self.spec_k:
                # the DRAFT cache must ingest the same prompt (pages never
                # shortcut it — the draft pool does not exist, and the
                # draft is cheap enough that full-prompt draft prefill
                # still wins): one draft chunk rides along per target
                # chunk, and the tail (page-hit admissions cover fewer
                # live target chunks than the draft's full count)
                # completes with the LAST target chunk, so both models
                # flip active in the same host call.
                if chunk_i == 0:
                    self._draft_chunks[slot] = 0
                    # a page load just before this admission shortcuts
                    # the draft too (self-spec; load_prefix staged the
                    # count)
                    self._draft_start[slot] = self._draft_pending[slot]
                    self._draft_pending[slot] = 0
                dstart = int(self._draft_start[slot])
                n_d = self.n_chunks(len(prompt) - dstart)
                if self._draft_chunks[slot] < n_d:
                    self._draft_prefill_chunk(slot, prompt,
                                              int(self._draft_chunks[slot]),
                                              dstart)
                if last:
                    while self._draft_chunks[slot] < n_d:
                        self._draft_prefill_chunk(
                            slot, prompt, int(self._draft_chunks[slot]),
                            dstart)
            if not last:
                return None
            with self._annotation("dtf.engine.prefill.readback"):
                toks, dones, _ = self._read(out, 1)
                tok, done = int(toks[0]), bool(dones[0])
            if self.spec_k:
                self._spec_index[slot] = len(prompt)
                self._spec_tok[slot] = tok
            return tok, done

    def prefill_wait(self) -> None:
        """Block until the prefill chunks dispatched so far have run on the
        device: a wait on the last one's output, which copies nothing and
        counts no read. A scheduler that runs decode ahead calls it last in
        a tick, after the tick's decode step is dispatched, so a tick still
        ends with its own prefill done and the next tick's first-token
        read waits for one decode step at most, never for a tick of
        chunks."""
        out, self._chunk_out = self._chunk_out, None
        if out is not None:
            out.block_until_ready()

    def _draft_prefill_chunk(self, slot: int, prompt: Sequence[int],
                             chunk_i: int, start: int = 0) -> None:
        """One fixed-width chunk of the DRAFT model's prefill into
        ``slot`` — the draft_prefill program, covering ``prompt[start:]``
        (``start`` > 0 only under self-speculation, where a page hit
        already landed the stem in the draft cache). The sampled first
        token is discarded: the request's sampling stream belongs to the
        verifier alone."""
        with self._annotation("dtf.engine.prefill.dispatch"):
            self._draft_state, _ = self._draft_prefill_c(
                self._draft_params, self._live(self._draft_state),
                self._chunk_operand(slot, prompt, chunk_i, start))
        self.counters["draft_prefill_chunks"] += 1
        self._draft_chunks[slot] += 1

    def prefill(self, slot: int, prompt: Sequence[int], *, start: int = 0,
                **sampling) -> tuple[int, bool]:
        """Admit a request into ``slot``: stream its whole prompt (minus
        ``start`` page-loaded tokens) through the compiled chunk program
        and sample the first token. Returns ``(first_token, done)``."""
        n = self.n_chunks(len(prompt) - start)
        if n == 0:
            # the per-chunk validation never runs on an empty prompt —
            # fail here, not with a None return at the caller's unpack
            raise ValueError(
                f"prompt length 0 must be in [1, {self.max_len - 1}]")
        out = None
        for i in range(n):
            out = self.prefill_chunk_into(slot, prompt, i, start=start,
                                          **sampling)
        return out

    def decode(self, *, trace_ids: Optional[Sequence[int]] = None,
               handle=None):
        """One masked token step across all slots, read on the host.

        Without a draft model: ``(tokens [n_slots], done [n_slots])`` as
        host arrays — the one device→host sync per generated token (EOS
        and delivery decisions live on the host). ``handle`` names a step
        :meth:`decode_begin` dispatched earlier, which this call collects
        (the run-ahead tick's read of step k-1); without one the step is
        dispatched here, ``decode_end(decode_begin())``, the synchronous
        step. Every plain step's tokens reach the host through this one
        method on either tick, so whatever stands in for ``decode`` (a
        fault injector, a proxy) sees all of them. With ``spec_k > 0`` the
        step is SPECULATIVE — draft_all proposes k tokens per slot, the
        verify program scores all k+1 positions in one pass — and the
        return is ``(tokens [n_slots, k+1], done [n_slots, k+1],
        n_emit [n_slots])``: the scheduler delivers ``tokens[s, :n_emit
        [s]]`` per slot (still one sync per TICK, now worth up to k+1
        tokens). ``trace_ids`` (scheduler-threaded) names the requests
        this step serves in the XPlane annotation; a collect writes none
        (the run-ahead tick annotates its dispatch and collect together)."""
        if handle is not None:
            return self.decode_end(handle)
        with self._annotation(
                "dtf.serve.decode",
                trace_ids="" if trace_ids is None
                else ",".join(map(str, trace_ids))):
            if self.spec_k:
                return self._decode_spec()
            return self.decode_end(self.decode_begin())

    @property
    def can_run_ahead(self) -> bool:
        """Whether a scheduler may dispatch step n+1 (:meth:`decode_begin`)
        before it collects step n (``decode(handle=)``): every plain
        engine (``spec_k == 0``); the speculative step's acceptance
        accounting reads each step before the next."""
        return not self.spec_k

    def decode_begin(self):
        """Dispatch one masked token step across all slots (non-speculative
        engines) and start its packed vector's copy to the host; returns
        the handle ``decode(handle=)`` collects. Nothing is read: the host
        may dispatch further calls before it collects this one."""
        with self._annotation("dtf.engine.decode.dispatch"):
            self._state, out = self._decode_c(
                self._params, self._live(self._state))
            out.copy_to_host_async()
        self.counters["decode_steps"] += 1
        return out

    def decode_end(self, handle):
        """Collect the step :meth:`decode_begin` dispatched (for
        :meth:`decode`, through which every caller collects): THE blocking
        read of its packed vector, ``(tokens [n_slots], done [n_slots])``
        as host arrays. The step's telemetry scalars and a routed-expert
        model's counts are noted here, when the step is collected, for
        :meth:`take_samples`."""
        with self._annotation("dtf.engine.decode.readback"):
            toks, done, tail = self._read(handle, self.n_slots)
        n = len(_STEP_OUT_NAMES)
        path, cache_positions, active = (int(x) for x in tail[:n])
        self._step_out = (path, cache_positions, active)
        if self.cfg.experts is not None:
            self._note_moe(cache_positions, active, tail[n:])
        return toks, done

    def _note_moe(self, cache_positions: int, active: int,
                  layer_stats: np.ndarray) -> None:
        """A routed-expert model's decode step, from the step's one
        readback: ``picks`` are the (token, expert) pairs one expert
        layer routed; the rest are means over the expert layers
        (``held_pairs`` / ``held_touched``: the pairs that landed on the
        experts the layer holds, and how many of those got a token).
        ``counters`` sums over layers (divide by ``decode_steps`` x layers
        for means)."""
        picks = active * self.cfg.experts.top_k
        touched, max_load, held_pairs, held_touched = layer_stats.reshape(
            len(_MOE_LAYER_STATS), -1)
        self.counters["moe_decode_picks"] += picks * len(touched)
        self.counters["moe_experts_touched"] += int(touched.sum())
        self.counters["moe_max_expert_load"] += int(max_load.sum())
        if not picks:
            return
        self.moe_samples = {
            "picks": picks,
            "experts_touched": float(touched.mean()),
            "max_load_over_mean": float(max_load.mean())
            * self.cfg.experts.num_experts / picks,
            "held_pairs": float(held_pairs.mean()),
            "held_touched": float(held_touched.mean()),
            "cache_positions": cache_positions}

    def take_samples(self) -> dict:
        """What the last engine call left for a telemetry object, by span
        name less its ``serve_`` (docs/OBSERVABILITY.md section 7), taken
        once: ``moe_samples``, and after a decode step ``sampler_greedy``
        (1.0 where no live slot sampled, else 0.0) and
        ``decode_attn_live_pct`` (the positions the step's attention has to
        read, each active slot's cached ones and its new one, as a share
        of active slots x ``max_len``: what ``ops/decode_attention.py``
        reads where it engages, of what the XLA spelling reads). The
        step's scalars came with its tokens (:meth:`decode`); the sampler's
        path is counted into ``counters["sampler_steps_*"]`` HERE, where a
        telemetry object asks."""
        samples = {f"moe_{name}": value
                   for name, value in (self.moe_samples or {}).items()}
        self.moe_samples = None
        step, self._step_out = self._step_out, None
        if step is not None:
            path, cache_positions, active = step
            self.counters[f"sampler_steps_{_SAMPLER_PATHS[path]}"] += 1
            samples["sampler_greedy"] = float(path == 0)
            if active:
                samples["decode_attn_live_pct"] = (
                    100.0 * (cache_positions + active)
                    / (active * self.max_len))
        return samples

    def draft_propose(self):
        """One draft_all dispatch: k greedy proposals per slot off the
        draft model's own cache (rolled to the verifier's accepted
        boundary via the host-mirrored sync index first). Split out of
        :meth:`decode` so chaos injectors can wrap it — a poisoned draft
        must fall back to plain decode, not error the request."""
        self._draft_state, props = self._draft_c(
            self._draft_params, self._live(self._draft_state),
            self._spec_tok, self._spec_index)
        self.counters["host_operands"] += 2
        self.counters["draft_steps"] += 1
        return props

    def _decode_spec(self):
        with self._annotation("dtf.engine.decode.dispatch"):
            try:
                props = self.draft_propose()
            except EngineStateLost:
                raise       # no draft cache to fall back from: retire
            except Exception as e:  # noqa: BLE001 — a draft failure must
                # not fail requests: the verify step is CORRECT for
                # arbitrary proposals (worst case it emits 1 token — plain
                # decode), so null proposals are the fallback, not an
                # error.
                log.warning("draft_all failed (%r); falling back to plain "
                            "decode this tick", e)
                self.counters["draft_fallbacks"] += 1
                props = np.zeros((self.n_slots, self.spec_k), np.int32)
                self.counters["host_operands"] += 1
            self._state, out = self._decode_c(
                self._params, self._live(self._state), props)
            out.copy_to_host_async()
        self.counters["decode_steps"] += 1
        with self._annotation("dtf.engine.decode.readback"):
            toks, dones, tail = self._read(
                out, self.n_slots * (self.spec_k + 1))
        toks = toks.reshape(self.n_slots, -1)
        dones = dones.reshape(self.n_slots, -1)
        # the verify step reports its sampler's path alone
        self._step_out, n_emit = (int(tail[0]), 0, 0), tail[1:]
        # host mirrors advance from values this readback carries anyway
        live = n_emit > 0
        self._spec_index = self._spec_index + n_emit
        picked = toks[np.arange(self.n_slots), np.maximum(n_emit, 1) - 1]
        self._spec_tok = np.where(live, picked,
                                  self._spec_tok).astype(np.int32)
        self.counters["spec_proposed"] += int(self.spec_k * live.sum())
        self.counters["spec_accepted"] += int((n_emit[live] - 1).sum())
        return toks, dones, n_emit

    def probe(self) -> None:
        """One decode dispatch with the outputs discarded — the Router's
        PROBATION health probe: a re-admitted replica proves the engine
        answers at normal latency before live traffic gambles on it.
        Deliberately routes through :meth:`decode` (NOT the raw compiled
        executable): anything wrapping the instance's ``decode`` — the
        serve fault injectors, a future engine proxy — must be observed
        by the probe, or a still-wedged replica would probe clean and be
        re-admitted into an oscillation. Same compiled ``decode_all``
        program (no retrace — ``trace_counts`` stays pinned); stale slots
        advance like any other masked step, which is safe by the PR 4
        reset contract: an admitted request fully reinitializes its slot,
        so probes can never perturb request tokens."""
        self.decode()
        self.counters["probe_decodes"] += 1

    # -------------------------------------------------- weight hot-swap

    @staticmethod
    def _check_tree_like(new, old, what: str) -> None:
        """New weights must be drop-in for the compiled executables:
        same tree, same shapes, same dtypes — anything else would need a
        recompile, which hot-swap exists to avoid. Fails loudly naming
        the first offending leaf."""
        nf, ntd = jax.tree_util.tree_flatten_with_path(new)
        of, otd = jax.tree_util.tree_flatten_with_path(old)
        if ntd != otd:
            raise ValueError(
                f"swap_params: new {what} tree structure differs from "
                "the served tree — hot-swap needs the SAME architecture "
                "(a different config is a new engine, not a swap)")
        for (pn, n), (_, o) in zip(nf, of):
            if (tuple(n.shape) != tuple(o.shape)
                    or np.dtype(n.dtype) != np.dtype(o.dtype)):
                raise ValueError(
                    f"swap_params: {what} leaf "
                    f"{jax.tree_util.keystr(pn)} is {tuple(n.shape)}/"
                    f"{np.dtype(n.dtype)}, the served engine expects "
                    f"{tuple(o.shape)}/{np.dtype(o.dtype)}")

    def set_param_version(self, version: int) -> None:
        """Stamp the version of the weights this engine was BUILT with
        (serving a published version from startup) — no swap, no
        counters; call before any traffic so record stamps and page
        epochs carry the real version instead of 0."""
        self.param_version = int(version)

    def swap_params(self, params: PyTree, *, draft_params: PyTree = None,
                    version: Optional[int] = None) -> int:
        """Hot-swap the served weights in place — ZERO recompiles.

        The new tree is validated against the served one (same
        structure/shapes/dtypes, :meth:`_check_tree_like`) and re-placed
        onto the OLD leaves' shardings (``jax.device_put`` per leaf —
        single device and TP mesh alike), so the AOT executables accept
        the new arrays exactly like the old ones: ``trace_counts`` stays
        pinned (counter-tested in tests/test_serve_swap.py).

        Caller contract (the Router's rolling swap enforces it): the
        engine must be DRAINED — no queued/admitting/running request —
        when this runs; an in-flight stream would otherwise mix logits
        of two versions. Stale slot state needs no cleanup (the PR 4
        reset contract: an admitted request fully reinitializes its
        slot), and the prefix-page EPOCH bump makes every page the old
        weights produced unreachable from this engine.

        For a SPEC engine the draft rides the same transaction:
        ``draft_params`` swaps it explicitly; under SELF-speculation the
        new target tree is the draft by definition; a distinct draft
        with no new weights keeps proposing from the old ones — still
        correct (the verifier samples every delivered token; proposals
        only set the acceptance rate), just logged.

        ``version`` stamps :attr:`param_version` (the publish version);
        default is the previous version + 1. Returns the new version."""
        self._check_tree_like(params, self._params, "params")
        # re-place onto the OLD leaves' shardings: the committed layout
        # the AOT executables were compiled against, whatever devices/
        # mesh that is — a host array, a differently-placed array or a
        # resharded tree all land right
        placed = jax.tree.map(
            lambda n, o: jax.device_put(n, o.sharding),
            params, self._params)
        placed_draft = None
        if self.spec_k:
            if draft_params is not None:
                self._check_tree_like(draft_params, self._draft_params,
                                      "draft_params")
                placed_draft = jax.tree.map(
                    lambda n, o: jax.device_put(n, o.sharding),
                    draft_params, self._draft_params)
            elif self._draft_self:
                # self-speculation: draft ≡ target architecture AND
                # weights — the one placed tree swaps both sides
                placed_draft = placed
            else:
                log.info(
                    "swap_params: spec engine keeps its previous draft "
                    "weights (no draft_params passed for a distinct "
                    "draft model) — acceptance may drop, correctness "
                    "cannot (the verifier owns the token stream)")
        # THE transaction: target, draft and version flip together,
        # between compiled dispatches (the pump loop is single-threaded)
        self._params = placed
        if placed_draft is not None:
            self._draft_params = placed_draft
        self.param_version = (int(version) if version is not None
                              else self.param_version + 1)
        self.counters["param_swaps"] += 1
        return self.param_version

    # ----------------------------------------------------- prefix page API

    def prefix_match(self, prompt: Sequence[int]):
        """Admission-time lookup: the longest cached page chain exactly
        matching a prefix of ``prompt`` AT THIS ENGINE's param version
        (pages are epoch-keyed — KV from other weight versions is
        unreachable), PINNED until :meth:`release_prefix` (the scheduler
        releases on slot evict). None on a miss or with the page cache
        off."""
        if self._prefix is None:
            return None
        prompt = tuple(int(t) for t in prompt)
        h = self._prefix.acquire(prompt, epoch=self.param_version)
        if h is None:
            self.counters["prefix_miss_tokens"] += len(prompt)
        else:
            self.counters["prefix_hit_tokens"] += h.n_tokens
            self.counters["prefix_miss_tokens"] += len(prompt) - h.n_tokens
        return h

    def _ids_buf(self, ids: Sequence[int]) -> np.ndarray:
        buf = np.zeros((self.max_len // self.page_size,), np.int32)
        buf[:len(ids)] = ids
        return buf

    def load_prefix(self, slot: int, handle) -> None:
        """Gather a pinned chain's pages into ``slot``'s leading cache
        positions — ONE compiled dispatch for the whole chain, replacing
        ``n_tokens/prefill_chunk`` transformer chunks of prefill work (the
        saving the page cache exists for; a per-page spelling would give
        most of it back as host dispatch overhead)."""
        ids = [e.page_id for e in handle.entries]
        self._state = self._page_load_c(
            self._live(self._state), self._pages, np.int32(slot),
            self._ids_buf(ids), np.int32(len(ids)))
        self.counters["host_operands"] += 3
        self.counters["pages_loaded"] += len(ids)
        if self.spec_k and self._draft_self:
            # self-speculation: the draft cache is struct-identical, so
            # the SAME compiled gather lands the chain there too — the
            # draft's prefill then covers only the uncached tail, like
            # the target's (no draft page programs exist or are needed)
            self._draft_state = self._page_load_c(
                self._live(self._draft_state), self._pages, np.int32(slot),
                self._ids_buf(ids), np.int32(len(ids)))
            self.counters["host_operands"] += 3
            self._draft_pending[slot] = handle.n_tokens
            self.counters["draft_pages_loaded"] += len(ids)

    def save_prefix_pages(self, slot: int, prompt: Sequence[int]) -> None:
        """After a request's LAST prefill chunk: register every full page
        of its prompt not yet in the pool and scatter them out of the
        slot's freshly written KV — one dispatch however many pages are
        new. Stops silently when the pool is exhausted by pinned/parented
        pages — saving is an optimization, never a blocker."""
        if self._prefix is None:
            return
        prompt = tuple(int(t) for t in prompt)
        epoch = self.param_version
        full = len(prompt) // self.page_size
        have, parent = self._prefix.longest(prompt, cap=full, epoch=epoch)
        # save admission: only prefixes traffic has repeated are worth a
        # dispatch — a unique tail page would cost host overhead and a
        # pool slot for KV nobody will ever hit (pages.py docstring)
        full = have + self._prefix.save_eligible(prompt, have, full,
                                                 epoch=epoch)
        ids = []
        for i in range(have, full):
            ent = self._prefix.reserve(prompt[:(i + 1) * self.page_size],
                                       parent, epoch=epoch)
            if ent is None:
                break
            ids.append(ent.page_id)
            parent = ent
        if not ids:
            return
        buf = self._ids_buf([0] * have + ids)
        self._pages = self._page_save_c(
            self._live(self._state), self._pages, np.int32(slot), buf,
            np.int32(have), np.int32(have + len(ids)))
        self.counters["host_operands"] += 4
        self.counters["pages_saved"] += len(ids)

    def release_prefix(self, handle) -> None:
        """Unpin an admission chain (call exactly once, on slot evict)."""
        if handle is not None:
            self._prefix.release(handle)

    def warm_page_programs(self) -> None:
        """Run both page programs once with no-op operands (n_valid=0
        load, empty [lo, hi) save window) so first-call backend overhead
        lands outside any timed window — the bench A/B warms every
        program before its measured section, and this keeps the calling
        convention next to the programs it warms instead of spelled out
        in the bench. No cache row or pool page changes. No-op with the
        cache off."""
        if self._prefix is None:
            return
        buf = self._ids_buf([])
        self._state = self._page_load_c(self._live(self._state), self._pages,
                                        np.int32(0), buf, np.int32(0))
        self._pages = self._page_save_c(self._state, self._pages,
                                        np.int32(0), buf, np.int32(0),
                                        np.int32(0))

    def prefix_stats(self) -> dict:
        """Page-cache aggregates (empty dict with the cache off)."""
        if self._prefix is None:
            return {}
        return {**self._prefix.stats,
                "pages": self.n_pages - self._prefix.n_free,
                "pages_free": self._prefix.n_free,
                # live pins should drain to 0 once every admitted request
                # released its handle — a leak here is a requeue/evict
                # path dropping the pages.py refcount contract
                "pinned": self._prefix.pinned()}

    def cache_bytes(self) -> int:
        """Resident KV footprint: slot cache + page pool (a MOUNTED shared
        pool counts on its owning engine only — summing a fleet must not
        multiply one pool by the replica count), all layers; with a draft
        model, its slot cache too."""
        leaves = jax.tree.leaves(self._state["cache"])
        if self._prefix is not None and self._owns_pages:
            leaves += jax.tree.leaves(self._pages)
        if self.spec_k:
            leaves += jax.tree.leaves(self._draft_state["cache"])
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in leaves)


def engine_state_struct(cfg: gpt.GPTConfig, *, n_slots: int, max_len: int,
                        mesh: Optional[Mesh] = None) -> PyTree:
    """Abstract engine state (slot-batched KV cache + per-slot arrays)
    exactly as a ``DecodeEngine(cfg, n_slots=, max_len=)`` would allocate
    it — ShapeDtypeStructs with the engine's shardings attached.  The
    introspection hook the HBM fit planner (``python -m dtf_tpu.analysis
    fit``) prices per-slot KV bytes from (bf16 vs int8 via
    ``cfg.kv_cache_dtype``), and the page-pool twin of
    :func:`dtf_tpu.serve.pages.pool_abstract` — eval_shape only, no
    device memory, no compile."""
    dec = dataclasses.replace(cfg, decode_len=max_len, slot_decode=True,
                              chunked_prefill=False)
    return _state_struct(dec, n_slots, mesh)


def _prefill_operand_struct(prefill_chunk: int) -> jax.ShapeDtypeStruct:
    """The prefill programs' one host operand, abstractly
    (:data:`_PREFILL_HEAD`, then the chunk's token columns)."""
    return jax.ShapeDtypeStruct((len(_PREFILL_HEAD) + prefill_chunk,),
                                jnp.int32)


def program_table(cfg: gpt.GPTConfig, *, n_slots: int, max_len: int,
                  mesh: Optional[Mesh] = None, prefill_chunk: int = 8,
                  spec_k: int = 0,
                  draft_cfg: Optional[gpt.GPTConfig] = None,
                  counts: Optional[dict] = None,
                  abs_trees: Optional[dict] = None):
    """Build the serve tier's core programs as fenced executor Programs.

    THE one construction (ISSUE 18): ``DecodeEngine.__init__`` AOT-
    compiles exactly this table (passing ``abs_trees`` derived from its
    real arrays so restored-checkpoint shardings are honored), and the
    analysis step views below enumerate the same table built from rule-
    derived abstract trees — the fenced graph and the served graph are
    the same construction, not hand-kept twins.

    Returns ``(programs, models)``: ``programs`` maps ``decode`` (the
    verify program when ``spec_k > 0`` — verify IS spec decode),
    ``prefill``, and with a draft ``draft_prefill`` + ``draft``, to
    :class:`dtf_tpu.core.executor.Program`s with their operand abstracts
    registered; ``models`` the matching flax modules. ``counts`` is the
    shared trace fence dict (``DecodeEngine.trace_counts``). ``probe()``
    needs no entry: it replays the compiled decode program. Every program
    DONATES its state operand (argument 1) — the in-place cache update;
    the module docstring has what that asks of the caller.
    """
    base = dataclasses.replace(cfg, decode_len=max_len, slot_decode=False,
                               chunked_prefill=False)
    dec_cfg = dataclasses.replace(base, slot_decode=True)
    abs_trees = dict(abs_trees or {})
    abs_params = abs_trees.get("params")
    if abs_params is None:
        abs_params = _abs_params(base, mesh)
    abs_state = abs_trees.get("state")
    if abs_state is None:
        abs_state = _state_struct(dec_cfg, n_slots, mesh)
    models = {
        "decode": gpt.GPT(dec_cfg, mesh),
        "prefill": gpt.GPT(
            dataclasses.replace(base, chunked_prefill=True), mesh),
    }
    #: prefill_into_slot's one host operand, shared by both prefill
    #: programs (and re-bundled by prefill_step_view/disagg_step_view)
    ops_abs = _prefill_operand_struct(prefill_chunk)
    jit_kw = {}
    rep = None
    if mesh is not None:
        # pin the OUTPUT state to the input layout: GSPMD would otherwise
        # pick its own output shardings, and the next call of the AOT
        # executable would reject the resharded state; every program's
        # second output is its one packed vector (_pack_out)
        rep = NamedSharding(mesh, P())
        state_sh = jax.tree.map(lambda s: s.sharding, abs_state)
        jit_kw["out_shardings"] = (state_sh, rep)
    donate_state = {"donate": True, "donate_args": (1,)}
    programs = {}
    if spec_k:
        props_abs = jax.ShapeDtypeStruct((n_slots, spec_k), jnp.int32,
                                         sharding=rep)
        executor.program(
            "decode", _build_verify_fn(models["decode"], spec_k),
            counts=counts, jit_kw=jit_kw, **donate_state,
            abstract_args=(abs_params, abs_state, props_abs),
            table=programs)
    else:
        executor.program(
            "decode", _build_decode_fn(models["decode"]),
            counts=counts, jit_kw=jit_kw, **donate_state,
            abstract_args=(abs_params, abs_state), table=programs)
    executor.program(
        "prefill", _build_prefill_fn(models["prefill"]),
        counts=counts, jit_kw=jit_kw, **donate_state,
        abstract_args=(abs_params, abs_state, ops_abs),
        table=programs)
    if spec_k:
        dbase = dataclasses.replace(draft_cfg, decode_len=max_len,
                                    slot_decode=False, chunked_prefill=False)
        ddec_cfg = dataclasses.replace(dbase, slot_decode=True)
        models["draft"] = gpt.GPT(ddec_cfg, mesh)
        models["draft_prefill"] = gpt.GPT(
            dataclasses.replace(dbase, chunked_prefill=True), mesh)
        abs_dparams = abs_trees.get("draft_params")
        if abs_dparams is None:
            abs_dparams = _abs_params(dbase, mesh)
        abs_dstate = abs_trees.get("draft_state")
        if abs_dstate is None:
            abs_dstate = _state_struct(ddec_cfg, n_slots, mesh)
        draft_kw = {}
        if mesh is not None:
            dstate_sh = jax.tree.map(lambda s: s.sharding, abs_dstate)
            draft_kw["out_shardings"] = (dstate_sh, rep)
        vec_abs = jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=rep)
        executor.program(
            "draft_prefill", _build_prefill_fn(models["draft_prefill"]),
            counts=counts, jit_kw=draft_kw, **donate_state,
            abstract_args=(abs_dparams, abs_dstate, ops_abs),
            table=programs)
        executor.program(
            "draft", _build_draft_fn(models["draft"], spec_k),
            counts=counts, jit_kw=draft_kw, **donate_state,
            abstract_args=(abs_dparams, abs_dstate, vec_abs, vec_abs),
            table=programs)
    return programs, models


def page_program_table(abs_state: PyTree, pool_abs: PyTree, *,
                       n_pages: int, max_len: int, kv_page_size: int,
                       mesh: Optional[Mesh] = None,
                       counts: Optional[dict] = None):
    """The two page programs (``save``/``load``) as fenced Programs —
    same shared-construction contract as :func:`program_table`, split out
    because the page pool is optional (``prefix_pages > 0``) and carries
    its own trace fence (``DecodeEngine.page_trace_counts``)."""
    save_kw, load_kw = {}, {}
    if mesh is not None:
        # same pin rationale as program_table: the AOT executables must
        # keep the pool/state in their committed layouts
        save_kw["out_shardings"] = jax.tree.map(
            lambda s: s.sharding, pool_abs)
        load_kw["out_shardings"] = jax.tree.map(
            lambda s: s.sharding, abs_state)
    s_i32 = jax.ShapeDtypeStruct((), jnp.int32)
    ids_abs = jax.ShapeDtypeStruct((max_len // kv_page_size,), jnp.int32)
    programs = {}
    executor.program(
        "save", _build_page_save_fn(n_pages), counts=counts,
        jit_kw=save_kw,
        abstract_args=(abs_state, pool_abs, s_i32, ids_abs, s_i32, s_i32),
        table=programs)
    executor.program(
        "load", _build_page_load_fn(), counts=counts, jit_kw=load_kw,
        donate=True,    # the state (argument 0); the pool is only read
        abstract_args=(abs_state, pool_abs, s_i32, ids_abs, s_i32),
        table=programs)
    return programs


def decode_step_view(cfg: gpt.GPTConfig, *, n_slots: int, max_len: int,
                     mesh: Optional[Mesh] = None):
    """The engine's decode program as an analyzable step:
    ``(program, abstract_params, abstract_state)`` — the ``decode``
    entry of :func:`program_table`, so the comms-budget fence covers the
    serving decode graph exactly as ``DecodeEngine`` compiles it (same
    model, same state layout, same shardings, same construction)."""
    programs, _ = program_table(cfg, n_slots=n_slots, max_len=max_len,
                                mesh=mesh)
    prog = programs["decode"]
    abs_params, abs_state = prog.abstract_args
    # the fenced view is the table's body WITHOUT the engine's output
    # pins: the pin exists for AOT reuse (reject resharded state), but it
    # costs extra replication all-gathers the served per-tick graph never
    # runs (the engine feeds each output straight back in) — pinning here
    # would charge the comms budget for transfers that don't happen.
    # For the same reason the view does not donate: without the pin GSPMD
    # shards some outputs (rng, done) its own way, and a donated leaf
    # whose output has another layout aliases nothing. Donation soundness
    # of the PINNED table programs is checked on this mesh by
    # tests/test_memory_analysis.py instead.
    view = executor.program("decode_view", prog.body,
                            abstract_args=(abs_params, abs_state))
    return view, abs_params, abs_state


def _abs_params(cfg: gpt.GPTConfig, mesh: Optional[Mesh]) -> PyTree:
    """Abstract TP-sharded param tree — identical across the decode /
    prefill / page model variants (architecture config, not cache mode)."""
    from dtf_tpu.core.sharding import tree_shardings

    model = gpt.GPT(dataclasses.replace(cfg, slot_decode=True), mesh)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32)))
    abs_params = shapes["params"]
    if mesh is not None:
        abs_params = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            abs_params, tree_shardings(abs_params, mesh, gpt.tp_rules))
    return abs_params


def prefill_step_view(cfg: gpt.GPTConfig, *, n_slots: int, max_len: int,
                      prefill_chunk: int = 8, mesh: Optional[Mesh] = None):
    """The engine's prefill program as an analyzable step:
    ``(jitted_fn, abstract_params, abstract_operand_bundle)`` — the same
    ``prefill_into_slot`` body ``DecodeEngine`` AOT-compiles (slot slice →
    chunked-prefill model → slot write-back → first-token sample), with
    the state and the call's one host operand bundled into one pytree so
    the analysis runner's two-argument step shape fits. The comms-budget
    fence this enables covers the known sharded-prefill resharding cost
    (engine docstring: GSPMD respells the traced-index slot slice as a
    resharding of the touched cache leaves) — previously documented, now
    pinned."""
    programs, _ = program_table(cfg, n_slots=n_slots, max_len=max_len,
                                mesh=mesh, prefill_chunk=prefill_chunk)
    prog = programs["prefill"]
    abs_params, abs_state, ops_abs = prog.abstract_args
    ops = {"state": abs_state, "ops": ops_abs}

    def step(params, ops):
        return prog.body(params, ops["state"], ops["ops"])

    jit_kw = {}
    if mesh is not None:
        # the engine pins the output state to the input layout (its AOT
        # executables reject resharded state) — the fenced graph must be
        # the SAME pinned program, not GSPMD's free choice
        jit_kw["out_shardings"] = (
            jax.tree.map(lambda s: s.sharding, abs_state),
            NamedSharding(mesh, P()))
    return (executor.program("prefill_view", step, jit_kw=jit_kw,
                             abstract_args=(abs_params, ops)),
            abs_params, ops)


def page_step_view(cfg: gpt.GPTConfig, *, n_slots: int, max_len: int,
                   kv_page_size: int, n_pages: int,
                   mesh: Optional[Mesh] = None):
    """The page programs as one analyzable step: ``page_load`` of a
    pinned chain followed by ``page_save`` of the new pages — an
    admission tick, exactly the two extra AOT programs a
    ``prefix_pages > 0`` engine compiles (their own trace fence,
    ``page_trace_counts``). Returned as ``(jitted_fn, state_bundle,
    operand_bundle)``; the fence pins the batched gather/scatter
    collectives so a pool-layout change that makes GSPMD move whole
    cache leaves per admission fails tier-1 first."""
    from dtf_tpu.serve import pages as pages_lib

    if max_len % kv_page_size:
        raise ValueError(
            f"kv_page_size={kv_page_size} does not divide "
            f"max_len={max_len} (same rule as DecodeEngine)")
    dec_cfg = dataclasses.replace(cfg, decode_len=max_len, slot_decode=True)
    state_abs = _state_struct(dec_cfg, n_slots, mesh)
    pool_abs = pages_lib.pool_abstract(state_abs["cache"], n_pages,
                                       kv_page_size, mesh)
    pages = page_program_table(state_abs, pool_abs, n_pages=n_pages,
                               max_len=max_len, kv_page_size=kv_page_size,
                               mesh=mesh)
    load_fn = pages["load"].body
    save_fn = pages["save"].body

    def step(bundle, ops):
        st = load_fn(bundle["state"], bundle["pool"], ops["slot"],
                     ops["ids"], ops["n_valid"])
        pool = save_fn(st, bundle["pool"], ops["slot"], ops["ids"],
                       ops["lo"], ops["hi"])
        return {"state": st, "pool": pool}

    jit_kw = {}
    if mesh is not None:
        # same pin as the engine's page programs (load_kw/save_kw): the
        # fence must compile the pinned layouts, not GSPMD's free choice
        jit_kw["out_shardings"] = {
            "state": jax.tree.map(lambda s: s.sharding, state_abs),
            "pool": jax.tree.map(lambda s: s.sharding, pool_abs)}
    s_i32 = jax.ShapeDtypeStruct((), jnp.int32)
    ops = {"slot": s_i32,
           "ids": jax.ShapeDtypeStruct((max_len // kv_page_size,),
                                       jnp.int32),
           "n_valid": s_i32, "lo": s_i32, "hi": s_i32}
    bundle = {"state": state_abs, "pool": pool_abs}
    return (executor.program("page_view", step, jit_kw=jit_kw,
                             abstract_args=(bundle, ops)),
            bundle, ops)


def spec_step_view(cfg: gpt.GPTConfig, draft_cfg: gpt.GPTConfig, *,
                   n_slots: int, max_len: int, spec_k: int,
                   mesh: Optional[Mesh] = None):
    """The SPECULATIVE tick (``draft_all`` ∘ ``verify``) as one
    analyzable step — the two extra graphs a spec engine compiles, fenced
    together the way ``page_step_view`` fences an admission tick. The
    comms budget pins both the draft's unrolled k-step loop and the
    (k+1)-wide verify pass (its TP all-reduces, the per-row cache
    scatter, the rollback assignment); the memory fence prices the
    k-token verify temp and the draft's resident cache — the numbers
    ``analysis fit`` needs to answer "max slots with spec on"."""
    programs, _ = program_table(cfg, n_slots=n_slots, max_len=max_len,
                                mesh=mesh, spec_k=spec_k,
                                draft_cfg=draft_cfg)
    verify_fn = programs["decode"].body
    draft_fn = programs["draft"].body

    def step(bundle, ops):
        dstate, props = draft_fn(bundle["draft_params"],
                                 bundle["draft_state"],
                                 ops["tok"], ops["sync_index"])
        state, out = verify_fn(bundle["params"], bundle["state"], props)
        return {"state": state, "draft_state": dstate, "out": out}

    abs_params, abs_state = programs["decode"].abstract_args[:2]
    abs_dparams, abs_dstate = programs["draft"].abstract_args[:2]
    bundle = {"params": abs_params, "draft_params": abs_dparams,
              "state": abs_state, "draft_state": abs_dstate}
    vec = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    ops = {"tok": vec, "sync_index": vec}
    jit_kw = {}
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        jit_kw["out_shardings"] = {
            "state": jax.tree.map(lambda s: s.sharding, abs_state),
            "draft_state": jax.tree.map(lambda s: s.sharding, abs_dstate),
            "out": rep}
    return (executor.program("spec_view", step, jit_kw=jit_kw,
                             abstract_args=(bundle, ops)),
            bundle, ops)


def disagg_step_view(cfg: gpt.GPTConfig, *, n_slots: int, max_len: int,
                     prefill_chunk: int, kv_page_size: int, n_pages: int,
                     mesh: Optional[Mesh] = None):
    """The PREFILL-replica admission tick of a disaggregated fleet
    (``prefill_into_slot`` ∘ ``page_save``): the handoff-producing
    composition — a dedicated prefill replica's whole job is to run
    prompt chunks and scatter the resulting KV pages into the shared
    pool for decode replicas to gather. Fencing the composition pins the
    transport's collective structure (the TP projections of the chunk
    plus the pool scatter over data shards) so a layout change that
    turns the handoff into whole-leaf traffic fails tier-1 first."""
    if max_len % kv_page_size:
        raise ValueError(
            f"kv_page_size={kv_page_size} does not divide "
            f"max_len={max_len} (same rule as DecodeEngine)")
    base = dataclasses.replace(cfg, decode_len=max_len, slot_decode=False,
                               chunked_prefill=False)
    programs, _ = program_table(cfg, n_slots=n_slots, max_len=max_len,
                                mesh=mesh, prefill_chunk=prefill_chunk)
    prefill_fn = programs["prefill"].body
    state_abs = _state_struct(
        dataclasses.replace(base, slot_decode=True), n_slots, mesh)
    from dtf_tpu.serve import pages as pages_lib

    pool_abs = pages_lib.pool_abstract(state_abs["cache"], n_pages,
                                       kv_page_size, mesh)
    pages = page_program_table(state_abs, pool_abs, n_pages=n_pages,
                               max_len=max_len, kv_page_size=kv_page_size,
                               mesh=mesh)
    save_fn = pages["save"].body

    def step(bundle, ops):
        state, out = prefill_fn(bundle["params"], bundle["state"],
                                ops["prefill"])
        pool = save_fn(state, bundle["pool"], ops["slot"], ops["ids"],
                       ops["lo"], ops["hi"])
        return {"state": state, "pool": pool, "out": out}

    jit_kw = {}
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        jit_kw["out_shardings"] = {
            "state": jax.tree.map(lambda s: s.sharding, state_abs),
            "pool": jax.tree.map(lambda s: s.sharding, pool_abs),
            "out": rep}
    s_i32 = jax.ShapeDtypeStruct((), jnp.int32)
    ops = {
        "prefill": _prefill_operand_struct(prefill_chunk), "slot": s_i32,
        "ids": jax.ShapeDtypeStruct((max_len // kv_page_size,), jnp.int32),
        "lo": s_i32, "hi": s_i32,
    }
    bundle = {"params": _abs_params(base, mesh), "state": state_abs,
              "pool": pool_abs}
    return (executor.program("disagg_view", step, jit_kw=jit_kw,
                             abstract_args=(bundle, ops)),
            bundle, ops)
