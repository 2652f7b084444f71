"""Serve step factories — incl. the sealed-weights path where the
HBM-resident model stays ciphertext and is decrypted on use (the paper's
threat model: plaintext never crosses the probe-able boundary), and the
paged-cache continuous-batching steps where the KV cache gets the same
treatment."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import sealed_store as SS
from repro.models import paged as PG
from repro.models import transformer as T
from repro.serve import sampling as SM


# --------------------------------------------------------------------------
# device-resident scheduler state
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SchedState:
    """All per-slot scheduler state the decode hot loop touches, as one
    device-resident pytree.

    The host scheduler never rebuilds these arrays per tick (the PR 2
    engine paid eleven ``asarray`` round-trips per decode step); instead it
    drives the jitted transitions below — ``admit`` / ``evict`` write whole
    slot rows by scatter, ``decode_tick`` / ``chunk_step`` advance the
    state functionally with donated buffers. The only device->host copy in
    steady state is the sampled token vector.

    tables (S, MB) i32   block table per slot (0 = scratch block)
    lengths (S,) i32     tokens currently in the cache per slot
    wc (NB,) u32         per-pool-block write counters (sealing nonces)
    run (S,) bool        slot is in the decode phase (prefill finished)
    last_tok (S,) i32    token to feed at the next decode tick
    counts (S,) i32      tokens generated so far (PRNG stream index)
    key_data (S, 2) u32  per-request PRNG key
    temp/topk/topp       per-request sampling params
    routes (2,) u32      MoE counters since start: (token, expert) pairs of
                         real tokens that landed on held experts, and all
                         their pairs (zero for dense models)
    """
    tables: jax.Array
    lengths: jax.Array
    wc: jax.Array
    run: jax.Array
    last_tok: jax.Array
    counts: jax.Array
    key_data: jax.Array
    temp: jax.Array
    topk: jax.Array
    topp: jax.Array
    routes: jax.Array


def sched_init(slots: int, max_blocks: int, num_blocks: int) -> SchedState:
    s, mb = slots, max_blocks
    return SchedState(
        tables=jnp.zeros((s, mb), jnp.int32),
        lengths=jnp.zeros((s,), jnp.int32),
        wc=jnp.zeros((num_blocks,), jnp.uint32),
        run=jnp.zeros((s,), bool),
        last_tok=jnp.zeros((s,), jnp.int32),
        counts=jnp.zeros((s,), jnp.int32),
        key_data=jnp.zeros((s, 2), jnp.uint32),
        temp=jnp.zeros((s,), jnp.float32),
        topk=jnp.zeros((s,), jnp.int32),
        topp=jnp.ones((s,), jnp.float32),
        routes=jnp.zeros((2,), jnp.uint32),
    )


def _routes(state: SchedState, updates):
    """``state.routes`` plus the per-layer counters a MoE model's update
    records carry (unchanged for other models)."""
    per_layer = [u["routes"] for u in updates if "routes" in u]
    if not per_layer:
        return state.routes
    return state.routes + sum(jnp.sum(r, axis=0) for r in per_layer)


def make_admit():
    """Jitted slot admission: scatter whole rows for up to A slots at once.
    Padded entries carry slot_id == S and drop. ``lengths`` starts at the
    shared-prefix token count (0 without prefix sharing); the slot enters
    in the chunked-prefill phase (run=False)."""
    def admit(state: SchedState, slot_ids, tables, n_shared, key_data,
              temp, topk, topp):
        at = lambda arr: arr.at[slot_ids]
        z = jnp.zeros_like(slot_ids)
        return dataclasses.replace(
            state,
            tables=state.tables.at[slot_ids].set(tables, mode="drop"),
            lengths=at(state.lengths).set(n_shared, mode="drop"),
            run=at(state.run).set(False, mode="drop"),
            last_tok=at(state.last_tok).set(z, mode="drop"),
            counts=at(state.counts).set(z, mode="drop"),
            key_data=state.key_data.at[slot_ids].set(key_data, mode="drop"),
            temp=at(state.temp).set(temp, mode="drop"),
            topk=at(state.topk).set(topk, mode="drop"),
            topp=at(state.topp).set(topp, mode="drop"),
        )
    return admit


def make_evict():
    """Jitted slot eviction: zero the finished slots' rows so the decode
    tick's masked lanes read benign state. Padded slot ids drop."""
    def evict(state: SchedState, slot_ids):
        at = lambda arr: arr.at[slot_ids]
        z = jnp.zeros_like(slot_ids)
        return dataclasses.replace(
            state,
            tables=state.tables.at[slot_ids].set(0, mode="drop"),
            lengths=at(state.lengths).set(z, mode="drop"),
            run=at(state.run).set(False, mode="drop"),
            last_tok=at(state.last_tok).set(z, mode="drop"),
            counts=at(state.counts).set(z, mode="drop"),
            temp=at(state.temp).set(0.0, mode="drop"),
            topk=at(state.topk).set(z, mode="drop"),
            topp=at(state.topp).set(1.0, mode="drop"),
        )
    return evict


def make_cow(cfg: ModelConfig, cache_seal):
    """Jitted copy-on-write: duplicate pool blocks src -> dst (re-keyed in
    flight for sealed pools) and bump the destination write counters.
    Returns (pools, state, ok) — ok goes False if a verified source block
    fails its MAC (always True without cache verification)."""
    def cow(pools, state: SchedState, src, dst, mask):
        pools, wc, ok = PG.copy_blocks(cfg, cache_seal, pools, state.wc,
                                       src, dst, mask)
        return pools, dataclasses.replace(state, wc=wc), ok
    return cow


def make_chunk_step(cfg: ModelConfig, materialize, cache_seal):
    """Jitted chunked-prefill step: run one fixed-width chunk for up to A
    slots (gathered by slot id; padded rows have chunk_len == 0 and write
    nothing), seal the chunk's K/V into the slots' blocks, and on each
    row's final chunk sample the request's first token.

    Returns (tok, cok, state, pools): ``cok`` is the (S,) per-slot cache
    integrity verdict — failed rows of the gather scatter back True so
    untouched slots read clean. It is a traced constant when cache
    verification is off, so the no-verify graph is unchanged. (The weight
    image is verified in its own dispatch — ``ServeEngine._verify_weights``
    — not here: it is immutable during serving, and re-hashing every
    weight inside every tick would price each step without changing the
    trust story.)"""
    def chunk_step(tensors, pools, state: SchedState, slot_ids, tokens,
                   chunk_len, is_final):
        params = materialize(tensors)
        s = state.lengths.shape[0]
        sl = jnp.minimum(slot_ids, s - 1)
        tables = state.tables[sl]
        lengths = state.lengths[sl]
        logits, updates, okr = PG.chunk_logits(cfg, params, pools, tables,
                                               lengths, state.wc, tokens,
                                               chunk_len, cache_seal)
        pools, wc = PG.append_tokens(cfg, cache_seal, pools, updates,
                                     tables, lengths, chunk_len, state.wc)
        keys = SM.fold_token_keys(state.key_data[sl],
                                  jnp.zeros_like(chunk_len))
        tok = SM.sample_logits(logits, keys, state.temp[sl],
                               state.topk[sl], state.topp[sl])
        tok = jnp.where(is_final, tok, 0)
        fin = lambda v: jnp.where(is_final, v, 0)
        cok = jnp.ones((s,), bool).at[slot_ids].set(okr, mode="drop")
        state = dataclasses.replace(
            state,
            wc=wc,
            routes=_routes(state, updates),
            lengths=state.lengths.at[slot_ids].add(chunk_len, mode="drop"),
            run=state.run.at[slot_ids].set(is_final, mode="drop"),
            counts=state.counts.at[slot_ids].set(
                fin(jnp.ones_like(chunk_len)), mode="drop"),
            last_tok=state.last_tok.at[slot_ids].set(fin(tok), mode="drop"),
        )
        return tok, cok, state, pools
    return chunk_step


def make_decode_tick(cfg: ModelConfig, materialize, cache_seal):
    """Jitted whole-batch decode tick: one dispatch advances every running
    slot a token — logits over the paged view, sealed tail-block append,
    per-request sampling. Non-running slots have chunk counts 0: they write
    nothing and keep their state.

    Returns (tok, cok, state, pools) — see ``make_chunk_step``; only
    tok/cok cross back to the host per tick."""
    def tick(tensors, pools, state: SchedState):
        params = materialize(tensors)
        tokens = state.last_tok[:, None]
        logits, updates, cok = PG.decode_logits(cfg, params, pools,
                                                state.tables, state.lengths,
                                                state.wc, tokens, cache_seal,
                                                live=state.run[:, None])
        cnt = state.run.astype(jnp.int32)
        pools, wc = PG.append_tokens(cfg, cache_seal, pools, updates,
                                     state.tables, state.lengths, cnt,
                                     state.wc)
        keys = SM.fold_token_keys(state.key_data, state.counts)
        tok = SM.sample_logits(logits, keys, state.temp, state.topk,
                               state.topp)
        tok = jnp.where(state.run, tok, state.last_tok)
        cok = cok | ~state.run            # only running slots can fail
        state = dataclasses.replace(
            state, wc=wc, routes=_routes(state, updates),
            lengths=state.lengths + cnt,
            counts=state.counts + cnt,
            last_tok=tok,
        )
        return tok, cok, state, pools
    return tick


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, cache, batch, pos):
        return T.decode_step(cfg, params, cache, batch, pos)
    return decode_step


def make_paged_prefill(cfg: ModelConfig, materialize, cache_seal):
    """Ragged admission prefill: run a right-padded (A, S_bucket) batch,
    seal its KV into the admitted slots' pool blocks, and sample each
    request's first token (generation index 0)."""
    def prefill(tensors, pools, tokens, true_len, block_tables, wc,
                key_data, temperature, top_k, top_p):
        params = materialize(tensors)
        logits, cache = PG.prefill_logits(cfg, params, tokens, true_len)
        pools = PG.prefill_write(cfg, cache_seal, pools, cache,
                                 block_tables, wc)
        keys = SM.fold_token_keys(key_data, jnp.zeros_like(true_len))
        tok = SM.sample_logits(logits, keys, temperature, top_k, top_p)
        return tok, logits, pools
    return prefill


def make_sealed_decode_step(cfg: ModelConfig, sp: SS.SealedParams,
                            key_bytes: bytes, fused: bool = True):
    """Decode with in-graph decryption: the jit boundary receives ciphertext
    ``SealedTensor`` leaves. With ``fused`` (default), matmul-shaped leaves
    stay sealed all the way into ``kernels.sealed_matmul`` and decrypt
    in-register; with ``fused=False`` every leaf decrypts eagerly first
    (the paper-faithful 3x-weight-traffic baseline)."""
    def decode_step(tensors, cache, batch, pos):
        sp2 = SS.SealedParams(tensors, sp.plans, sp.treedef, sp.seal)
        params = (SS.fused_params if fused else SS.unseal_params)(
            sp2, key_bytes)
        return T.decode_step(cfg, params, cache, batch, pos)
    return decode_step
