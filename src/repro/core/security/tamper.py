"""Deterministic memory-tamper fault injection against the sealed serve path.

The SEAL threat model gives the adversary physical access to accelerator
memory: they can flip ciphertext bits, replay stale images, roll back write
counters (forcing OTP reuse on the next re-seal — see
``attacks.otp_reuse_leak``), and relocate blocks. Encryption alone detects
none of these; the co-located Carter–Wegman MACs (``core.mac``) must catch
all four. This module is the test harness that proves it: a
``TamperInjector`` is a ``runtime.fault.FaultInjectionHook`` the
``ServeEngine`` calls at the top of every scheduler step, mutating the
HBM-image stand-ins (the engine's pool arrays / device counters) exactly the
way a memory adversary would — between dispatches, never through the sealed
write path.

Fault classes (``FAULT_KINDS``):

* ``bitflip``  — flip one ciphertext bit in a resident cache block. Under
  CTR sealing this flips exactly that plaintext bit (a *targeted* model/
  cache corruption, not noise); the block's tag no longer matches.
* ``replay``   — snapshot a tail block (ciphertext AND tag — a coherent
  stale image), let the engine re-write it a few times, then restore the
  snapshot. The stale tag was minted under the old write counter; the
  verifier derives the pad from the trusted current counter.
* ``rollback`` — decrement the DEVICE-side write counter of a block,
  leaving the host mirror (the trust boundary) untouched. The stored tag
  binds the true counter, so reads under the rolled-back counter fail; the
  engine's recovery path resyncs the device counters from the mirror,
  which is what prevents the subsequent re-seal from reusing an OTP.
* ``relocate`` — swap two resident blocks *together with their tags* (the
  strongest variant: the hash matches, only the pad's address binding can
  catch the move).

Every injector is deterministic: it fires at a fixed scheduler step (with
deferral until the target slot actually has resident data), records a
``TamperEvent``, and never consults a clock or RNG.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from repro.runtime.fault import FaultInjectionHook

FAULT_KINDS = ("bitflip", "replay", "rollback", "relocate")


@dataclasses.dataclass
class TamperEvent:
    """One recorded mutation of the sealed memory image."""
    kind: str
    step: int                      # scheduler step the mutation landed on
    slot: int                      # victim serve slot
    block: int                     # pool block mutated (src block for swaps)
    layer: int = 0                 # superblock row inside the pool
    word: int = 0                  # word index (bitflip)
    bit: int = 0                   # bit index (bitflip)
    detail: str = ""


class TamperInjector(FaultInjectionHook):
    """Inject ONE fault of ``kind`` into a serve engine's sealed cache.

    The injector waits until ``start_step`` and until the victim slot is in
    the decode phase with resident data (deferring otherwise, so drivers
    need not time admission), then mutates the pool/state arrays in place
    of the HBM image. ``events`` records what fired; ``fired`` is the
    one-shot latch. A ``replay`` arms at fire time and restores the stale
    snapshot ``replay_delay`` steps later (the block must be re-written in
    between for the replay to be observable — the injector defers arming
    until the victim's tail block is going to absorb that many appends).
    """

    def __init__(self, kind: str, *, slot: int = 0, start_step: int = 3,
                 layer: int = 0, word: int = 7, bit: int = 3,
                 replay_delay: int = 2):
        assert kind in FAULT_KINDS, kind
        self.kind = kind
        self.slot = slot
        self.start_step = start_step
        self.layer = layer
        self.word = word
        self.bit = bit
        self.replay_delay = replay_delay
        self.fired = False
        self.events: List[TamperEvent] = []
        self._step = 0
        self._snap: Optional[tuple] = None      # (restore_step, block, blobs)

    # -------------------------------------------------- pool mutation

    @staticmethod
    def _mutate(engine, j: int, key: str, fn):
        """Host-side mutation of one pool array: copy out, edit, swap the
        new buffer in. The replaced array is a live jit output (safe to
        read); the engine's next dispatch donates the NEW buffer."""
        pools = list(engine._pools)
        pj = dict(pools[j])
        arr = np.array(pj[key])
        fn(arr)
        pj[key] = jnp.asarray(arr)
        pools[j] = pj
        engine._pools = tuple(pools)

    def _victim(self, engine):
        """(tail_block_index, length) once the victim slot is decoding with
        at least one resident block; None while deferring."""
        if engine._active[self.slot] is None:
            return None
        if engine._pending[self.slot] is not None:
            return None                      # still prefilling
        length = int(engine._lengths[self.slot])
        if length <= 0:
            return None
        return (length - 1) // engine.block_size, length

    # -------------------------------------------------- hook

    def on_step(self, engine) -> None:
        self._step += 1
        if self._snap is not None:
            self._restore(engine)
            return
        if self.fired or self._step < self.start_step:
            return
        tgt = self._victim(engine)
        if tgt is None:
            return
        bi, length = tgt
        getattr(self, f"_{self.kind}")(engine, bi, length)

    def _record(self, engine, block: int, **kw) -> TamperEvent:
        ev = TamperEvent(self.kind, self._step, self.slot, block, **kw)
        self.events.append(ev)
        self.fired = True
        return ev

    # -------------------------------------------------- fault classes

    def _bitflip(self, engine, bi: int, length: int) -> None:
        block = int(engine._tables[self.slot, bi])

        def flip(arr):
            words = arr.reshape(arr.shape[:2] + (-1,))     # a view
            words[self.layer, block, self.word] ^= np.uint32(1 << self.bit)

        self._mutate(engine, 0, "k", flip)
        self._record(engine, block, layer=self.layer, word=self.word,
                     bit=self.bit,
                     detail=f"ciphertext bit {self.bit} of word {self.word}")

    def _rollback(self, engine, bi: int, length: int) -> None:
        block = int(engine._tables[self.slot, bi])
        if int(engine._wc[block]) == 0:
            return                           # not yet written; defer
        wc = np.array(engine._state.wc)
        wc[block] -= np.uint32(1)
        engine._state = dataclasses.replace(engine._state,
                                            wc=jnp.asarray(wc))
        self._record(engine, block,
                     detail="device write counter decremented; host mirror "
                            "(trust boundary) untouched")

    def _replay(self, engine, bi: int, length: int) -> None:
        # the tail block absorbing the NEXT appends: it must stay the tail
        # for replay_delay more tokens so the snapshot goes stale
        bs = engine.block_size
        if length % bs + self.replay_delay > bs:
            return                           # would cross a block; defer
        r = engine._active[self.slot]
        if engine._mt_eff(r) - len(r.out) <= self.replay_delay + 1:
            return      # victim would finish before re-reading the stale
                        # image — the replay would land on a freed block
        block = int(engine._tables[self.slot, length // bs])
        blobs = {}
        for key in ("k", "v", "mac_k", "mac_v"):
            blobs[key] = np.array(engine._pools[0][key])[:, block].copy()
        self._snap = (self._step + self.replay_delay, block, blobs)
        self._record(engine, block,
                     detail=f"stale image snapshotted; restore in "
                            f"{self.replay_delay} steps")

    def _restore(self, engine) -> None:
        restore_step, block, blobs = self._snap
        if self._step < restore_step:
            return

        def put(key):
            def fn(arr):
                arr[:, block] = blobs[key]
            self._mutate(engine, 0, key, fn)

        for key in ("k", "v", "mac_k", "mac_v"):
            put(key)
        self._snap = None
        self.events.append(TamperEvent(
            "replay", self._step, self.slot, block,
            detail="stale (ciphertext, tag) image restored"))

    def _relocate(self, engine, bi: int, length: int) -> None:
        if bi < 1:
            return                           # need two resident blocks
        b0 = int(engine._tables[self.slot, 0])
        b1 = int(engine._tables[self.slot, 1])

        def swap(arr):
            tmp = arr[:, b0].copy()
            arr[:, b0] = arr[:, b1]
            arr[:, b1] = tmp

        for key in ("k", "v", "mac_k", "mac_v"):
            self._mutate(engine, 0, key, swap)
        # swap the counters too: a maximally careful adversary keeps every
        # co-located metadata word consistent — only the address binding in
        # the MAC pad can catch the move
        wc = np.array(engine._state.wc)
        wc[b0], wc[b1] = wc[b1], wc[b0]
        engine._state = dataclasses.replace(engine._state,
                                            wc=jnp.asarray(wc))
        engine._wc[b0], engine._wc[b1] = engine._wc[b1], engine._wc[b0]
        self._record(engine, b0,
                     detail=f"blocks {b0} <-> {b1} swapped with tags "
                            f"and counters")


def make_injectors(kinds, **kw) -> List[TamperInjector]:
    """One injector per named kind (comma-separated string or iterable)."""
    if isinstance(kinds, str):
        kinds = [k.strip() for k in kinds.split(",") if k.strip()]
    return [TamperInjector(k, **kw) for k in kinds]
