"""Serving engines over the sealed substrate.

``ServeEngine`` is a **continuous-batching** scheduler: a fixed set of
decode slots, per-slot admission and eviction at every step. All hot-loop
scheduler state (block tables, lengths, write counters, sampling state)
lives device-resident in a ``SchedState`` pytree (``serve/step.py``)
advanced by jitted transitions, so a decode tick is ONE dispatch with no
per-step host array rebuilds, and the only device->host copy in steady
state is the sampled token vector. Prompts prefill in fixed-size chunks
interleaved with decode ticks (no decode stall on long prompts), and with
``prefix_share=True`` identical prompt prefixes share sealed cache blocks
copy-on-write: counter-mode sealing derives a block's OTP from its pool
address + write counter, so N block tables can read the same ciphertext
block with zero re-encryption, and a slot only pays a copy (re-keyed in
flight, never plaintext in the pool) when it must append into a shared
tail block.

``GroupServeEngine`` is the old group-drain loop (prefill a group, decode
until every member finishes), kept as the benchmark baseline and as the
fallback for recurrent/SSD architectures, whose prefill state does not
tolerate the ragged right-padding the continuous path uses.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, SealConfig
from repro.core import sealed_store as SS
from repro.core.mac import SealedIntegrityError
from repro.models import cache as MC
from repro.models import paged as PG
from repro.models import transformer as T
from repro.models.cache import paged_pool_init
from repro.runtime.fault import StragglerTimeout
from repro.serve import sampling as SM
from repro.serve import step as ST


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                # (S,) int32
    max_tokens: int = 32
    eos: int = -1
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0
    t_done: float = 0.0
    retries: int = 0                  # integrity-failure re-prefills so far
    error: Optional[str] = None       # "integrity" once the retry budget is
                                      # exhausted; None on clean completion


# a host span on the profiler's clock; costs one object when nothing records
_span = jax.profiler.TraceAnnotation


def _jit(fn, donate):
    """jit with buffer donation: every transition rebinds the engine's
    ``_state``/``_pools`` to the outputs, so the inputs are dead and XLA
    can update the (large, pool-sized) buffers in place instead of
    copying them per dispatch."""
    return jax.jit(fn, donate_argnums=donate)


def _kv_bytes_per_token(cfg: ModelConfig) -> int:
    """Cache bytes one token holds in one layer: K and V, or MLA's one
    latent entry."""
    return len(MC.pool_streams(cfg)) * 4 * MC.kv_words_per_token(cfg)


class ServeEngine:
    """Continuous batcher over the paged, sealed KV cache.

    Device-side: one jitted decode tick for all slots, one jitted chunked
    prefill step, and scatter-style ``admit``/``evict``/``cow`` transitions
    over the resident ``SchedState``. Host-side: the refcounted block
    allocator, the prefix-sharing registry, the per-slot request
    bookkeeping, and *debug mirrors* of the device state (``_tables`` /
    ``_lengths`` / ``_wc`` / ``_counts`` — assertable via
    ``check_device_mirror``, never read by the hot loop).
    """

    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_len: int = 256, seal: Optional[SealConfig] = None,
                 key_bytes: bytes = bytes(range(32)), block_size: int = 16,
                 seal_cache: Optional[bool] = None,
                 admit_batch: Optional[int] = None, sample_seed: int = 0,
                 prefix_share: bool = False,
                 chunk_tokens: Optional[int] = None,
                 verify: bool = False, watchdog=None,
                 max_run_steps: Optional[int] = None, fault_hooks=(),
                 donate_params: bool = False):
        """``donate_params``: the engine owns ``params``; with sealed
        weights each plaintext leaf is deleted once sealed, so a full-width
        model fits beside its ciphertext and no plaintext copy stays in
        device memory. Off when the caller reuses ``params``."""
        assert cfg.frontend is None, "serving demo targets token archs"
        bad = [k for k in cfg.pattern if k not in ("attn", "local_attn")]
        if bad:
            raise ValueError(
                f"continuous batching needs attention-only patterns (got "
                f"{bad}); use GroupServeEngine for recurrent/SSD archs")
        self.cfg = cfg
        self.slots = batch_slots
        self.block_size = block_size
        self.max_len = -(-max_len // block_size) * block_size
        weights_sealed = seal is not None and seal.mode != "none"
        if seal_cache is None:
            seal_cache = weights_sealed
        self.seal_cache = seal_cache
        if verify and not (weights_sealed or seal_cache):
            raise ValueError("verify=True needs sealed weights and/or a "
                             "sealed cache — there is nothing to MAC")
        self.verify = verify
        if weights_sealed and verify and not seal.verify:
            seal = dataclasses.replace(seal, verify=True)
        self.seal = seal
        self.watchdog = watchdog
        self.max_run_steps = max_run_steps
        self.fault_hooks = tuple(fault_hooks)

        if weights_sealed:
            self.sealed = SS.seal_params(params, seal, key_bytes,
                                         consume=donate_params)
            meta = self.sealed

            def _materialize(tensors):
                sp = SS.SealedParams(tensors, meta.plans, meta.treedef,
                                     meta.seal)
                return SS.fused_params(sp, key_bytes)

            self._params_arg = meta.tensors
        else:
            self.sealed = None
            _materialize = lambda p: p
            self._params_arg = params

        if weights_sealed and verify:
            meta = self.sealed

            def _weight_verify(tensors):
                sp = SS.SealedParams(tensors, meta.plans, meta.treedef,
                                     meta.seal)
                return SS.verify_params(sp, key_bytes)

            # the weight image is immutable device state during serving, so
            # it gets its own jitted MAC sweep (fail-stop) at drain entry
            # rather than being re-hashed inside every chunk/decode dispatch
            self._wverify = jax.jit(_weight_verify)
        else:
            self._wverify = None
        self._has_wverify = self._wverify is not None
        self._wswept = False

        cache_seal = (SS.cache_seal_config(key_bytes, verify=verify)
                      if seal_cache else None)
        self._decode_fn = ST.make_decode_tick(cfg, _materialize, cache_seal)
        self._chunk_fn = ST.make_chunk_step(cfg, _materialize, cache_seal)
        self._decode = _jit(self._decode_fn, (1, 2))
        self._chunk = _jit(self._chunk_fn, (1, 2))
        self._admit_t = _jit(ST.make_admit(), (0,))
        self._evict_t = _jit(ST.make_evict(), (0,))
        self._cow_t = _jit(ST.make_cow(cfg, cache_seal), (0, 1))

        # device-resident scheduler state + host-side allocation
        s, mb = self.slots, self.max_len // block_size
        self.num_blocks = 1 + s * mb          # block 0 = scratch
        self._pools = paged_pool_init(cfg, self.num_blocks, block_size)
        self._state = ST.sched_init(s, mb, self.num_blocks)
        self._alloc = MC.BlockAllocator(self.num_blocks)
        self.prefix_share = prefix_share
        self._registry = (MC.PrefixRegistry(self._alloc, block_size)
                          if prefix_share else None)
        self.chunk_tokens = int(chunk_tokens or 2 * block_size)
        self._active: List[Optional[Request]] = [None] * s
        self._slot_blocks: List[List[int]] = [[] for _ in range(s)]
        self._pending: List[Optional[np.ndarray]] = [None] * s
        # host debug/assert mirrors of the device SchedState
        self._tables = np.zeros((s, mb), np.int32)
        self._lengths = np.zeros((s,), np.int32)
        self._wc = np.zeros((self.num_blocks,), np.uint32)
        self._last_tok = np.zeros((s,), np.int32)
        self._counts = np.zeros((s,), np.int32)
        self._admit_n = min(admit_batch or max(1, batch_slots // 4),
                            batch_slots)
        self._sample_seed = sample_seed
        self._next_rid = 0
        self.queue: List[Request] = []
        self._done: List[Request] = []

        kv_pt = 0 if seal_cache else (
            cfg.num_layers * s * self.max_len * _kv_bytes_per_token(cfg))
        w_pt = (self.sealed.plaintext_bytes_materialized() if self.sealed
                else sum(int(np.prod(x.shape)) * x.dtype.itemsize
                         for x in jax.tree.leaves(params)))
        self._stats = {
            "prefills": 0, "prefill_chunks": 0, "decode_steps": 0,
            "tokens": 0, "cow_copies": 0,
            "mac_checks": 0, "mac_failures": 0, "retries": 0,
            "shared_prefix_blocks": 0, "shared_prefix_tokens": 0,
            "kv_blocks_gathered": 0, "kv_blocks_walked": 0,
            "kv_blocks_resident": 0, "kv_blocks_reserved": 0,
            "fused_matmul_leaves": (len(self.sealed.fused_paths())
                                    if self.sealed else 0),
            "weights_plaintext_bytes_per_step": w_pt,
            "kv_plaintext_bytes_per_step": kv_pt,
            "plaintext_bytes_per_step": w_pt + kv_pt,
        }
        if cfg.moe is not None and cfg.moe.router == "sigmoid_bias":
            self._stats.update(moe_routes_held=0, moe_routes=0)

    # -------------------------------------------------- public API

    @property
    def stats(self) -> Dict[str, int]:
        """The engine's counters. A MoE model's route counters
        (``moe_routes_held``: pairs of real tokens that landed on the held
        experts; ``moe_routes``: all their pairs) accumulate on the device
        and are read here, never per tick."""
        if "moe_routes" in self._stats:
            held, total = (int(v) for v in np.asarray(self._state.routes))
            self._stats.update(moe_routes_held=held, moe_routes=total)
        return self._stats

    def submit(self, prompt, max_tokens: int = 32, eos: int = -1,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0) -> Request:
        prompt = np.asarray(prompt, np.int32)
        assert 1 <= len(prompt) < self.max_len, \
            f"prompt length {len(prompt)} vs max_len {self.max_len}"
        r = Request(self._next_rid, prompt, max_tokens, eos,
                    temperature, top_k, top_p, t_submit=time.time())
        self._next_rid += 1
        self.queue.append(r)
        return r

    @property
    def busy(self) -> bool:
        """True while any request is queued or holds a slot."""
        return bool(self.queue) or any(r is not None for r in self._active)

    @property
    def _free(self) -> List[int]:
        """Free pool blocks (allocator view; kept as a property for tests
        and introspection)."""
        return self._alloc._free

    def step(self) -> List[Request]:
        """Admit what fits, run one prefill chunk for admitted-but-pending
        prompts, advance every decoding slot one token; returns the
        requests that completed during this step. Registered fault hooks
        fire first — they model an adversary mutating the sealed memory
        image between dispatches.

        When the profiler records, the step and each of its phases leave
        a host span on the trace's clock: ``serve.step`` around
        ``serve.admit``, ``serve.chunk`` and ``serve.decode`` (each with
        a ``.readback`` child around the blocking token copy),
        ``serve.integrity`` and ``serve.evict``."""
        with _span("serve.step"):
            n0 = len(self._done)
            for hook in self.fault_hooks:
                hook.on_step(self)
            if not self._wswept:
                self._verify_weights()
            with _span("serve.admit"):
                self._admit()
            if any(p is not None for p in self._pending):
                self._chunk_tick()
            if any(r is not None and self._pending[i] is None
                   for i, r in enumerate(self._active)):
                self._decode_tick()
            return self._done[n0:]

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drain queue + in-flight work; returns the requests completed by
        this call (admission order can overtake across chunk schedules).

        Guards: ``max_steps`` (or the engine-level ``max_run_steps``)
        bounds the scheduler steps, and an attached ``StepWatchdog`` gets
        each step's wall-clock duration — either blowing raises
        ``StragglerTimeout`` instead of spinning forever on a stuck or
        pathologically slow drain."""
        n0 = len(self._done)
        limit = max_steps if max_steps is not None else self.max_run_steps
        self._verify_weights()          # fail-stop sweep at drain entry
        steps = 0
        while self.busy:
            before = (len(self.queue), self._stats["decode_steps"],
                      self._stats["prefills"])
            t0 = time.perf_counter()
            self.step()
            after = (len(self.queue), self._stats["decode_steps"],
                     self._stats["prefills"])
            assert after != before, "scheduler made no progress"
            steps += 1
            if self.watchdog is not None:
                self.watchdog.check(time.perf_counter() - t0)
            if limit is not None and steps >= limit and self.busy:
                raise StragglerTimeout(
                    f"serve drain exceeded {limit} steps with work still "
                    f"in flight ({len(self.queue)} queued)")
        return self._done[n0:]

    def compiled_hlo(self) -> Dict[str, str]:
        """The optimised HLO text of the decode tick and the chunk step as
        compiled for this engine's shapes (a persistent compile cache hands
        back the very programs that ran). Each instruction's ``op_name``
        metadata holds the scopes its operation ran in (``kv_view``,
        ``weight_decrypt``, ...), which a device trace's operation events
        do not carry; the keys are the programs' names in such a trace."""
        a, c = self._admit_n, self.chunk_tokens
        chunk = (np.zeros((a,), np.int32), np.zeros((a, c), np.int32),
                 np.zeros((a,), np.int32), np.zeros((a,), bool))
        return {"tick": self._decode.lower(
                    *self._decode_args()).compile().as_text(),
                "chunk_step": self._chunk.lower(
                    *self._decode_args(), *chunk).compile().as_text()}

    def check_device_mirror(self):
        """Debug/assert view: the host mirrors must track the device
        ``SchedState`` exactly (they are never read by the hot loop)."""
        st = self._state
        assert np.array_equal(np.asarray(st.tables), self._tables)
        assert np.array_equal(np.asarray(st.lengths), self._lengths)
        assert np.array_equal(np.asarray(st.wc), self._wc)
        assert np.array_equal(np.asarray(st.counts), self._counts)

    # -------------------------------------------------- scheduling

    def _mt_eff(self, r: Request) -> int:
        return max(1, min(r.max_tokens, self.max_len - len(r.prompt)))

    def _admit(self):
        bs, mb = self.block_size, self.max_len // self.block_size
        while self.queue:
            free_slots = [i for i, r in enumerate(self._active) if r is None]
            if not free_slots:
                return
            width = min(self._admit_n, len(free_slots))
            batch: List[tuple] = []
            cow_pairs: List[tuple] = []
            cow_slots: List[int] = []
            for r in list(self.queue):
                if len(batch) >= width:
                    break
                plen = len(r.prompt)
                if self._registry is not None:
                    full, partial, n_shared = self._registry.match(r.prompt)
                else:
                    full, partial, n_shared = [], None, 0
                # pin matched blocks before eviction can free them
                held = list(full) + ([partial[0]] if partial else [])
                self._alloc.incref(held)
                need = -(-(plen + self._mt_eff(r)) // bs) - len(full)
                if need > self._alloc.free_count and self._registry:
                    self._registry.evict_lru(need)
                priv = self._alloc.alloc(need)
                if priv is None:
                    self._alloc.decref(held)
                    break               # strict FIFO: head of queue blocks
                self.queue.remove(r)
                self._alloc.incref(full)   # the slot's own (durable) refs
                slot = free_slots[len(batch)]
                table = full + priv
                self._active[slot] = r
                self._slot_blocks[slot] = table
                self._pending[slot] = np.asarray(r.prompt[n_shared:],
                                                 np.int32)
                self._tables[slot] = 0
                self._tables[slot, :len(table)] = table
                self._lengths[slot] = n_shared
                self._counts[slot] = 0
                self._last_tok[slot] = 0
                if partial is not None:
                    cow_pairs.append((partial[0], priv[0]))
                    cow_slots.append(slot)
                    self._stats["cow_copies"] += 1
                self._stats["shared_prefix_blocks"] += (
                    len(full) + (1 if partial else 0))
                self._stats["shared_prefix_tokens"] += n_shared
                batch.append((slot, r, table, n_shared, held))
            if not batch:
                return
            a = self._admit_n
            sl = np.full((a,), self.slots, np.int32)
            tb = np.zeros((a, mb), np.int32)
            nsh = np.zeros((a,), np.int32)
            kd = np.zeros((a, 2), np.uint32)
            tp = np.zeros((a,), np.float32)
            tk = np.zeros((a,), np.int32)
            tpp = np.ones((a,), np.float32)
            for i, (slot, r, table, n_shared, _) in enumerate(batch):
                sl[i] = slot
                tb[i, :len(table)] = table
                nsh[i] = n_shared
                kd[i] = np.asarray(SM.request_key_data(self._sample_seed,
                                                       r.rid))
                tp[i], tk[i], tpp[i] = r.temperature, r.top_k, r.top_p
            self._state = self._admit_t(
                self._state, jnp.asarray(sl), jnp.asarray(tb),
                jnp.asarray(nsh), jnp.asarray(kd), jnp.asarray(tp),
                jnp.asarray(tk), jnp.asarray(tpp))
            if cow_pairs:
                src = np.zeros((a,), np.int32)
                dst = np.zeros((a,), np.int32)
                msk = np.zeros((a,), bool)
                for i, (s_b, d_b) in enumerate(cow_pairs):
                    src[i], dst[i], msk[i] = s_b, d_b, True
                    self._wc[d_b] += 1
                self._pools, self._state, cok = self._cow_t(
                    self._pools, self._state, jnp.asarray(src),
                    jnp.asarray(dst), jnp.asarray(msk))
                if self.verify and self.seal_cache:
                    self._stats["mac_checks"] += len(cow_pairs)
                    if not bool(cok):
                        # a shared source block failed its MAC: the copy
                        # would launder tampered content under a fresh tag,
                        # so drop the donor chains and retry the sharers
                        if self._registry is not None:
                            self._registry.purge_blocks(
                                [s for s, _ in cow_pairs])
                        for _, _, _, _, held in batch:
                            self._alloc.decref(held)
                        self._integrity_retry(cow_slots)
                        continue
            for _, _, _, _, held in batch:
                self._alloc.decref(held)   # slot refs live in _slot_blocks

    def _chunk_tick(self):
        """One chunked-prefill dispatch: up to admit-width pending slots
        each advance ``chunk_tokens`` prompt tokens; rows reaching the end
        of their prompt sample their first token and switch to decode."""
        a, c, bs = self._admit_n, self.chunk_tokens, self.block_size
        rows = [i for i, p in enumerate(self._pending) if p is not None][:a]
        if not rows:
            return
        with _span("serve.chunk"):
            sl = np.full((a,), self.slots, np.int32)
            toks = np.zeros((a, c), np.int32)
            cl = np.zeros((a,), np.int32)
            fin = np.zeros((a,), bool)
            for i, slot in enumerate(rows):
                pend = self._pending[slot]
                n = min(len(pend), c)
                sl[i] = slot
                toks[i, :n] = pend[:n]
                cl[i] = n
                fin[i] = n == len(pend)
            tok, cok, self._state, self._pools = self._chunk(
                self._params_arg, self._pools, self._state, jnp.asarray(sl),
                jnp.asarray(toks), jnp.asarray(cl), jnp.asarray(fin))
            self._stats["prefills"] += 1
            self._stats["prefill_chunks"] += len(rows)
            with _span("serve.chunk.readback"):
                tok = np.asarray(tok)
        cok_h = self._check_integrity(cok, len(rows))
        finished: List[int] = []
        failed: List[int] = []
        for i, slot in enumerate(rows):
            n = int(cl[i])
            r = self._active[slot]
            length = int(self._lengths[slot])
            # mirror the device's bumps whether or not the slot failed —
            # the mirror tracks what the dispatch DID, not what we trust
            for b in range(length // bs, (length + n - 1) // bs + 1):
                self._wc[self._tables[slot, b]] += 1
            self._lengths[slot] += n
            if cok_h is not None and not cok_h[slot]:
                failed.append(slot)
                continue
            if not fin[i]:
                self._pending[slot] = self._pending[slot][n:]
                continue
            self._pending[slot] = None
            if self._registry is not None:
                self._registry.register(r.prompt, self._slot_blocks[slot])
            nt = int(tok[i])
            self._counts[slot] = 1
            self._last_tok[slot] = nt
            r.out.append(nt)
            self._stats["tokens"] += 1
            if len(r.out) >= self._mt_eff(r) or nt == r.eos:
                finished.append(slot)
        if failed:
            self._integrity_retry(failed)
        if finished:
            self._evict_slots(finished)

    def _decode_args(self):
        """Current decode-tick operands (also used by jaxpr-level tests):
        everything is already device-resident — params, pools, SchedState."""
        return (self._params_arg, self._pools, self._state)

    def _kv_blocks(self, running: List[int]) -> Dict[str, int]:
        """What one decode tick reads of the cache, per layer, from the
        host mirrors: every slot's whole table, what a dense view gathers
        (``blocks_gathered``); the blocks the attention reads
        (``blocks_walked``: the paged attention kernel walks the running
        slots' resident blocks, a dense view gathers the whole tables —
        averaged over the pattern's layers); the blocks that hold the
        running slots' tokens (``blocks_resident``), the blocks every slot
        holds (``blocks_reserved``), and the running slots. Added to the
        cumulative ``kv_blocks_*`` stats."""
        bs = self.block_size
        resident = int(((self._lengths[running] + bs - 1) // bs).sum())
        full = self.slots * (self.max_len // bs)
        pattern = self.cfg.pattern
        kernel = sum(PG.kernel_reads(self.cfg, k) for k in pattern)
        kv = {"blocks_gathered": full,
              "blocks_walked": (kernel * resident
                                + (len(pattern) - kernel) * full)
                               // len(pattern),
              "blocks_resident": resident,
              "blocks_reserved": sum(len(b) for b in self._slot_blocks),
              "running": len(running)}
        for k in ("gathered", "walked", "resident", "reserved"):
            self._stats[f"kv_blocks_{k}"] += kv[f"blocks_{k}"]
        return kv

    def _decode_tick(self):
        running = [i for i, r in enumerate(self._active)
                   if r is not None and self._pending[i] is None]
        with _span("serve.decode", **self._kv_blocks(running)):
            tok, cok, self._state, self._pools = self._decode(
                *self._decode_args())
            self._stats["decode_steps"] += 1
            with _span("serve.decode.readback"):
                tok = np.asarray(tok)          # the ONLY d2h copy per tick
        cok_h = self._check_integrity(cok, len(running))
        bs = self.block_size
        finished: List[int] = []
        failed: List[int] = []
        for slot in running:
            r = self._active[slot]
            # mirror the device's seal-on-write counter bump of the tail
            # block the new K/V token landed in — for failed slots too:
            # the mirror tracks what the dispatch did, not what we trust
            pb = self._tables[slot, self._lengths[slot] // bs]
            self._wc[pb] += 1
            self._lengths[slot] += 1
            self._counts[slot] += 1
            if cok_h is not None and not cok_h[slot]:
                failed.append(slot)
                continue
            nt = int(tok[slot])
            self._last_tok[slot] = nt
            r.out.append(nt)
            self._stats["tokens"] += 1
            if len(r.out) >= self._mt_eff(r) or nt == r.eos:
                finished.append(slot)
        if failed:
            self._integrity_retry(failed)
        if finished:
            self._evict_slots(finished)

    # -------------------------------------------------- integrity

    def _verify_weights(self):
        """Full MAC sweep over the sealed weight image, as its OWN jitted
        dispatch (tracing it into every chunk/decode graph would price each
        tick with a whole-model hash for an image that is immutable device
        state during serving). Runs at ``run()`` entry and lazily once per
        engine via ``step()``; failure is fail-stop — the model is not
        trustworthy and no per-request recovery is possible."""
        self._wswept = True
        if not (self.verify and self._has_wverify):
            return
        self._stats["mac_checks"] += 1
        with _span("serve.verify_weights"):
            intact = bool(self._wverify(self._params_arg))
        if not intact:
            self._stats["mac_failures"] += 1
            raise SealedIntegrityError(
                "weights", "sealed weight image failed its MAC sweep — "
                "fail-stop, the model is not trustworthy")

    def _check_integrity(self, cok, n_checked: int):
        """Post-dispatch cache verdict handling: failures come back per
        slot for targeted recovery. Returns the host cache-verdict array,
        or None when verification is off (verdicts are traced constants).
        Weight integrity is handled separately in ``_verify_weights``."""
        if not self.verify:
            return None
        self._stats["mac_checks"] += n_checked
        with _span("serve.integrity"):
            return np.asarray(cok)

    def _integrity_retry(self, slots: List[int]):
        """Graceful degradation for cache MAC failures: fail ONLY the
        owning slots. Their registry chains are purged (a tampered shared
        block must not be re-served), their blocks are released, the
        device write counters are resynced from the trusted host mirror
        (counter rollback tampers the device array only), and each victim
        is re-prefilled once from the queue front under fresh counters;
        a second failure marks the request ``error="integrity"``. Slots
        that passed their check are untouched and decode bit-identically
        through the recovery."""
        with _span("serve.integrity"):
            self._stats["mac_failures"] += len(slots)
            victims = [self._active[s] for s in slots]
            if self._registry is not None:
                bad = [b for s in slots for b in self._slot_blocks[s]]
                self._registry.purge_blocks(bad)
            self._evict_slots(slots, complete=False)
            self._state = dataclasses.replace(
                self._state, wc=jnp.asarray(self._wc))
            for r in reversed(victims):
                if r.retries >= 1:
                    r.error = "integrity"
                    r.done = True
                    r.t_done = time.time()
                    self._done.append(r)
                    continue
                r.retries += 1
                r.out = []
                self._stats["retries"] += 1
                self.queue.insert(0, r)

    def _evict_slots(self, slots: List[int], complete: bool = True):
        """Batched slot teardown: one device evict dispatch zeroes the
        finished rows; the host drops block references (shared blocks
        survive while the registry or another reader holds them). With
        ``complete=False`` the requests are NOT marked done — the caller
        owns their fate (integrity retry / requeue)."""
        with _span("serve.evict"):
            ids = np.full((self.slots,), self.slots, np.int32)
            ids[:len(slots)] = slots
            self._state = self._evict_t(self._state, jnp.asarray(ids))
            for slot in slots:
                r = self._active[slot]
                if complete:
                    r.done = True
                    r.t_done = time.time()
                    self._done.append(r)
                self._alloc.decref(self._slot_blocks[slot])
                self._slot_blocks[slot] = []
                self._tables[slot] = 0
                self._lengths[slot] = 0
                self._counts[slot] = 0
                self._last_tok[slot] = 0
                self._active[slot] = None
                self._pending[slot] = None


class GroupServeEngine:
    """Group-drain baseline: prefill a fixed group, decode greedily until
    every member finishes — finished slots idle until the group drains.
    Kept for benchmark comparison and for recurrent/SSD architectures."""

    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_len: int = 256, seal: Optional[SealConfig] = None,
                 key_bytes: bytes = bytes(range(32)),
                 donate_params: bool = False):
        """``donate_params``: as for ``ServeEngine``."""
        assert cfg.frontend is None, "serving demo targets token archs"
        self.cfg = cfg
        self.slots = batch_slots
        self.max_len = max_len
        self.seal = seal
        if seal is not None and seal.mode != "none":
            self.sealed = SS.seal_params(params, seal, key_bytes,
                                         consume=donate_params)
            meta = self.sealed

            def _materialize(tensors):
                sp = SS.SealedParams(tensors, meta.plans, meta.treedef,
                                     meta.seal)
                return SS.fused_params(sp, key_bytes)

            def _decode(tensors, cache, batch, pos):
                return T.decode_step(cfg, _materialize(tensors), cache,
                                     batch, pos)

            def _prefill_one(tensors, batch):
                return T.prefill(cfg, _materialize(tensors), batch,
                                 self.max_len)

            self._params_arg = meta.tensors
            self._decode_fn = _decode
            self._prefill_fn = _prefill_one
        else:
            self.sealed = None
            self._params_arg = params
            self._decode_fn = lambda p, cache, batch, pos: T.decode_step(
                cfg, p, cache, batch, pos)
            self._prefill_fn = lambda p, batch: T.prefill(
                cfg, p, batch, self.max_len)
        self._decode = jax.jit(self._decode_fn)
        self._prefill = jax.jit(self._prefill_fn)
        self._next_rid = 0
        self.queue: List[Request] = []
        # same weights+KV split the continuous engine reports: the group
        # engine's contiguous cache is never sealed, so its KV image is
        # plaintext in full
        kv_pt = cfg.num_layers * batch_slots * max_len * \
            _kv_bytes_per_token(cfg)
        w_pt = (self.sealed.plaintext_bytes_materialized() if self.sealed
                else sum(int(np.prod(x.shape)) * x.dtype.itemsize
                         for x in jax.tree.leaves(params)))
        self.stats = {"prefills": 0, "decode_steps": 0, "tokens": 0,
                      "fused_matmul_leaves": (len(self.sealed.fused_paths())
                                              if self.sealed else 0),
                      "weights_plaintext_bytes_per_step": w_pt,
                      "kv_plaintext_bytes_per_step": kv_pt,
                      "plaintext_bytes_per_step": w_pt + kv_pt}

    def submit(self, prompt, max_tokens: int = 32, eos: int = -1) -> Request:
        r = Request(self._next_rid, np.asarray(prompt, np.int32), max_tokens,
                    eos, t_submit=time.time())
        self._next_rid += 1
        self.queue.append(r)
        return r

    @property
    def busy(self) -> bool:
        return bool(self.queue)

    def run(self) -> List[Request]:
        """Drain the queue; returns completed requests."""
        done: List[Request] = []
        while self.queue:
            group = self.queue[:self.slots]
            self.queue = self.queue[self.slots:]
            done.extend(self._run_group(group))
        return done

    def _run_group(self, group: List[Request]) -> List[Request]:
        b = len(group)
        plen = max(len(r.prompt) for r in group)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(group):          # left-pad-free: right align
            toks[i, plen - len(r.prompt):] = r.prompt
        logits, cache = self._prefill(self._params_arg,
                                      {"tokens": jnp.asarray(toks)})
        self.stats["prefills"] += 1
        nxt = np.asarray(jnp.argmax(logits, -1)).astype(np.int32)
        for i, r in enumerate(group):
            r.out.append(int(nxt[i]))
        pos = plen
        max_new = max(r.max_tokens for r in group)
        for _ in range(1, max_new):
            if pos >= self.max_len:
                break
            batch = {"tokens": jnp.asarray(nxt[:, None])}
            logits, cache, tok = self._decode(self._params_arg, cache, batch,
                                              jnp.int32(pos))
            self.stats["decode_steps"] += 1
            nxt = np.asarray(tok)
            pos += 1
            for i, r in enumerate(group):
                if r.done:
                    continue
                nt = int(nxt[i])
                r.out.append(nt)
                self.stats["tokens"] += 1
                if len(r.out) >= r.max_tokens or nt == r.eos:
                    r.done = True
                    r.t_done = time.time()
            if all(r.done for r in group):
                break
        for r in group:
            if not r.done:
                r.done = True
                r.t_done = time.time()
        return group
