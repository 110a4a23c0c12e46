# Copyright (c) 2026 PaddlePaddle-on-JAX growth authors.
#
# Licensed under the Apache License, Version 2.0 (the "License");
# you may not use this file except in compliance with the License.
# You may obtain a copy of the License at
#
#     http://www.apache.org/licenses/LICENSE-2.0
#
# Unless required by applicable law or agreed to in writing, software
# distributed under the License is distributed on an "AS IS" BASIS,
# WITHOUT WARRANTIES OR CONDITIONS OF ANY KIND, either express or implied.
"""In-memory buddy checkpointing (framework/buddy.py).

Battery layout mirrors the tier:

  * ring + codec units (no coordinator, no jax)
  * mailbox store: generation fencing, reset, owner+buddy eviction —
    on the base Coordinator and over the CoordServer wire (including
    survival across a primary SIGKILL: put_blob is replicated)
  * send/restore protocol units over LocalCoordinator, with the
    catalogued ``buddy.send`` / ``buddy.restore`` failpoints: a fault
    mid-send leaves the PREVIOUS generation restorable; a fault
    mid-restore falls the whole pod back (nobody adopts)
  * pod integration: warm buddy restore bitwise vs the uninterrupted
    reference; stale mailboxes and torn snapshots take the DISK rewind
    with the typed reason label
  * the retention-lock regression: checkpoint GC must never collect a
    step a concurrent scrub classification (the buddy tier's disk
    fallback elects from it) just called valid
"""

import contextlib
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.io as io_mod
from paddle_tpu import layers, optimizer
from paddle_tpu.framework import buddy, faultinject, resilience
from paddle_tpu.framework.coordination import (
    BlobTooLargeError, CoordinationError, FileCoordinator, HostLostError,
    LocalCoordinator, PodResilientTrainer, SocketCoordinator)
from paddle_tpu.framework.resilience import ResilientTrainer, RetryPolicy
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.framework.transport import (
    CoordServer, MailboxServer, mailbox_request, replicated_group)

pytestmark = [pytest.mark.faultinject, pytest.mark.pod]

POD_TIMEOUT_S = 300.0


@pytest.fixture(autouse=True)
def _clean_resilience():
    resilience.install(None)
    resilience.clear_events()
    yield
    resilience.install(None)
    resilience.clear_events()


def _fast_policy(**kw):
    kw.setdefault("base_delay_s", 0.0)
    kw.setdefault("jitter", 0.0)
    kw.setdefault("sleep", lambda s: None)
    return RetryPolicy(**kw)


def _run_hosts(fn, n):
    """Run fn(host_id) on n threads; returns ({hid: result}, {hid: exc})."""
    out, errs = {}, {}

    def worker(hid):
        try:
            out[hid] = fn(hid)
        except Exception as e:
            errs[hid] = e

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    return out, errs


def _arrays(seed=0, names=("w", "nested/b")):
    rng = np.random.RandomState(seed)
    return {n: rng.randn(3, 4).astype(np.float32) for n in names}


class _DictScope(object):
    """Minimal scope stand-in for adopt_arrays: find_var/set_var over a
    dict of host numpy arrays (no jax.Array, so adoption is raw)."""

    def __init__(self, **vars_):
        self.vars = dict(vars_)

    def find_var(self, name):
        return self.vars.get(name)

    def set_var(self, name, value):
        self.vars[name] = value


# ---------------------------------------------------------------------------
# ring assignment
# ---------------------------------------------------------------------------

def test_ring_buddies_shapes():
    # buddy(i) = next host on the sorted ring; every host is exactly one
    # host's buddy
    assert buddy.ring_buddies([0, 1, 2]) == {0: 1, 1: 2, 2: 0}
    # unsorted/duplicated membership normalizes to the same ring
    assert buddy.ring_buddies([2, 0, 2, 1]) == {0: 1, 1: 2, 2: 0}
    # sparse host ids: ring position, not id arithmetic
    assert buddy.ring_buddies([1, 5, 9]) == {1: 5, 5: 9, 9: 1}
    # two members buddy each other; fewer than two replicate nothing
    assert buddy.ring_buddies([3, 7]) == {3: 7, 7: 3}
    assert buddy.ring_buddies([4]) == {}
    assert buddy.ring_buddies([]) == {}
    assert buddy.buddy_of(5, [1, 5, 9]) == 9
    assert buddy.buddy_of(6, [1, 5, 9]) is None


def test_ring_rederives_on_membership_change():
    # elastic shrink: the ring re-closes around the hole with no
    # coordination — both neighbours of the lost host get new buddies
    before = buddy.ring_buddies([0, 1, 2, 3])
    after = buddy.ring_buddies([0, 2, 3])
    assert before[0] == 1 and before[3] == 0
    assert after == {0: 2, 2: 3, 3: 0}


# ---------------------------------------------------------------------------
# state-blob codec (shared with the disk checkpoint format)
# ---------------------------------------------------------------------------

def test_state_blob_roundtrip_zlib_bitwise():
    arrays = _arrays(seed=3)
    arrays["i"] = np.arange(7, dtype=np.int64)
    feed_state = {"cursor": 42, "lags": {"0": 1}}
    blob, raw, wire = io_mod.encode_state_blob(
        arrays, 11, compress="zlib", feed_state=feed_state)
    assert raw > 0 and wire > 0
    got, step, fs = io_mod.decode_state_blob(blob)
    assert step == 11 and fs == feed_state
    assert sorted(got) == sorted(arrays)     # "/" names survive npz
    for n in arrays:
        np.testing.assert_array_equal(got[n], arrays[n])
        assert got[n].dtype == arrays[n].dtype


def test_state_blob_q8_lossy_close():
    arrays = _arrays(seed=4, names=("w",))
    blob, raw, wire = io_mod.encode_state_blob(arrays, 2, compress="q8")
    got, step, fs = io_mod.decode_state_blob(blob)
    assert step == 2 and fs is None
    np.testing.assert_allclose(got["w"], arrays["w"], atol=0.05)
    with pytest.raises(ValueError):
        io_mod.encode_state_blob(arrays, 2, compress="lzma")


def test_state_blob_torn_payload_raises():
    blob, _, _ = io_mod.encode_state_blob(_arrays(), 1)
    torn = dict(blob, npz=blob["npz"][: len(blob["npz"]) // 2])
    with pytest.raises(Exception):
        io_mod.decode_state_blob(torn)


# ---------------------------------------------------------------------------
# mailbox store: base Coordinator
# ---------------------------------------------------------------------------

def test_put_blob_generation_fence_and_reset():
    co = LocalCoordinator(2, timeout_s=5.0)
    co.put_blob(0, 5, 1, {"npz": "aa"})
    # same gen: idempotent re-send, newer gen: overwrite in place
    co.put_blob(0, 5, 1, {"npz": "aa"})
    co.put_blob(0, 6, 1, {"npz": "bb"})
    assert co.get_blob(0)["gen"] == 6
    # a delayed put must never rewind below what a restore may have
    # adopted
    with pytest.raises(CoordinationError):
        co.put_blob(0, 4, 1, {"npz": "cc"})
    # reset: the post-disk-restore re-seed legitimately rewinds
    co.put_blob(0, 2, 1, {"npz": "dd"}, reset=True)
    rec = co.get_blob(0)
    assert rec["gen"] == 2 and rec["blob"] == {"npz": "dd"}
    # meta_only skips the payload (the election's cheap poll)
    meta = co.get_blob(0, meta_only=True)
    assert meta == {"gen": 2, "buddy": 1}
    assert co.get_blob(1) is None


def test_put_blob_fenced_owner_rejected_reads_stay_open():
    co = LocalCoordinator(2, timeout_s=5.0)
    co.put_blob(1, 3, 0, {"npz": "aa"})
    co.mark_lost(1, "declared")
    with pytest.raises(HostLostError):
        co.put_blob(1, 4, 0, {"npz": "bb"})
    # reads are unfenced: fetching a dead peer's last snapshot IS the
    # restore path
    assert co.get_blob(1)["gen"] == 3


def test_blob_eviction_needs_owner_and_buddy_both_lost():
    co = LocalCoordinator(3, timeout_s=5.0)
    for o, b in buddy.ring_buddies([0, 1, 2]).items():
        co.put_blob(o, 1, b, {"npz": "x%d" % o})
    # owner lost, buddy alive: the replica is exactly what the restore
    # needs — kept
    co.mark_lost(0, "died")
    assert co.get_blob(0) is not None
    # now the buddy dies too: the physical replica is gone — evicted
    co.mark_lost(1, "died")
    assert co.get_blob(0) is None
    # host 1's own mailbox survives (its buddy 2 is alive)
    assert co.get_blob(1) is not None
    assert co.get_blob(2) is not None


# ---------------------------------------------------------------------------
# mailbox store: over the CoordServer wire
# ---------------------------------------------------------------------------

def _socket_pod(stack, addr_or_addrs, n):
    cos = []
    for h in range(n):
        co = SocketCoordinator(addr_or_addrs, n, h, timeout_s=30.0,
                               poll_s=0.005, mesh_reinit=False,
                               hb_interval_s=0.1)
        stack.callback(co.close)
        cos.append(co)
    return cos


def test_blob_ops_over_socket():
    with contextlib.ExitStack() as stack:
        srv = CoordServer(3, hb_deadline_s=30.0).start()
        stack.callback(srv.close)
        cos = _socket_pod(stack, srv.address, 3)
        blob, _, _ = io_mod.encode_state_blob(_arrays(seed=9), 4)
        for o, b in buddy.ring_buddies([0, 1, 2]).items():
            cos[o].put_blob(o, 4, b, blob)
        # cross-host read + meta_only
        rec = cos[1].get_blob(0)
        assert rec["gen"] == 4 and rec["buddy"] == 1
        got, step, _ = io_mod.decode_state_blob(rec["blob"])
        assert step == 4
        np.testing.assert_array_equal(got["w"], _arrays(seed=9)["w"])
        meta = cos[1].get_blob(0, meta_only=True)
        assert meta == {"gen": 4, "buddy": 1} and "blob" not in meta
        assert cos[0].get_blob(7) is None
        # generation fence holds across the wire (server-side error)
        with pytest.raises(RuntimeError, match="rewind"):
            cos[0].put_blob(0, 3, 1, blob)
        cos[0].put_blob(0, 1, 1, blob, reset=True)
        assert cos[2].get_blob(0, meta_only=True)["gen"] == 1
        # fence + eviction: a fenced owner cannot publish; a mailbox
        # dies only when owner AND buddy are both gone
        cos[2].mark_lost(0, "died")
        with pytest.raises(HostLostError):
            cos[0].put_blob(0, 5, 1, blob)
        assert cos[2].get_blob(0) is not None     # buddy 1 still alive
        cos[2].mark_lost(1, "died")
        assert cos[2].get_blob(0) is None         # owner+buddy lost
        assert cos[2].get_blob(1) is not None     # its buddy 2 lives


def test_blob_survives_coordinator_failover():
    """put_blob is in _SYNC_CMDS: an acked snapshot is already on the
    warm standby — a primary SIGKILL right after the ack cannot lose
    the only copy of a dead host's state."""
    with contextlib.ExitStack() as stack:
        servers = replicated_group(2, n_members=2, hb_deadline_s=0.5)
        for s in servers:
            stack.callback(s.close)
        cos = _socket_pod(stack, [s.address for s in servers], 2)
        blob, _, _ = io_mod.encode_state_blob(_arrays(seed=5), 7)
        cos[0].put_blob(0, 7, 1, blob)
        cos[1].put_blob(1, 7, 0, blob)
        servers[0].kill()
        # the very next read fails over to the promoted standby and
        # finds the acked mailbox intact, payload and all
        rec = cos[1].get_blob(0)
        assert rec is not None and rec["gen"] == 7
        got, step, _ = io_mod.decode_state_blob(rec["blob"])
        assert step == 7
        np.testing.assert_array_equal(got["w"], _arrays(seed=5)["w"])
        with servers[1].state.lock:
            assert servers[1].state.role == "primary"


# ---------------------------------------------------------------------------
# send_snapshot: window-boundary sends + the buddy.send failpoint
# ---------------------------------------------------------------------------

def test_send_snapshot_roundtrip_records_gens_and_bytes():
    co = LocalCoordinator(2, timeout_s=5.0)
    a0, a1 = _arrays(seed=0), _arrays(seed=1)
    assert buddy.send_snapshot(co, 0, [0, 1], 3, a0)
    assert buddy.send_snapshot(co, 1, [0, 1], 3, a1)
    assert resilience.buddy_gens() == {0: 3, 1: 3}
    for hid, arrays in ((0, a0), (1, a1)):
        got, fs = buddy.fetch_and_decode(co, hid, 3)
        assert fs is None
        for n in arrays:
            np.testing.assert_array_equal(got[n], arrays[n])
    m = resilience.metrics()
    by_kind = {c["labels"]["kind"]: c["value"] for c in m["counters"]
               if c["name"].endswith("_buddy_snapshot_bytes_total")}
    assert by_kind.get("raw", 0) > 0 and by_kind.get("wire", 0) > 0
    gens = {g["labels"]["host"]: g["value"] for g in m["gauges"]
            if g["name"].endswith("_buddy_generation")}
    assert gens == {"0": 3.0, "1": 3.0}


def test_send_snapshot_skipped_below_two_members():
    co = LocalCoordinator(1, timeout_s=5.0)
    assert not buddy.send_snapshot(co, 0, [0], 1, _arrays())
    assert co.get_blob(0) is None
    assert not resilience.events("buddy_send_fail")


def test_fault_mid_send_keeps_previous_generation_restorable():
    """Satellite: the catalogued ``buddy.send`` failpoint fires BEFORE
    the put — the mailbox still holds the previous generation, bitwise
    decodable, and the send failure never raises into training."""
    co = LocalCoordinator(2, timeout_s=5.0)
    gen0, gen1 = _arrays(seed=10), _arrays(seed=11)
    assert buddy.send_snapshot(co, 0, [0, 1], 0, gen0)
    faultinject.arm(["buddy.send:raise=ConnectionError@1^0"])
    try:
        # host 0's next send tears mid-put: swallowed into an event
        assert not buddy.send_snapshot(co, 0, [0, 1], 1, gen1)
    finally:
        faultinject.disarm()
    fails = resilience.events("buddy_send_fail")
    assert fails and fails[-1]["host"] == 0 \
        and fails[-1]["error"] == "ConnectionError"
    # the PREVIOUS generation is still committed and decodes bitwise
    assert co.buddy_meta(0)["gen"] == 0
    got, _ = buddy.fetch_and_decode(co, 0, 0)
    for n in gen0:
        np.testing.assert_array_equal(got[n], gen0[n])
    # the gauge still reports the last PUBLISHED generation
    assert resilience.buddy_gens()[0] == 0
    # disarmed, the resend of the same boundary lands normally
    assert buddy.send_snapshot(co, 0, [0, 1], 1, gen1)
    assert co.buddy_meta(0)["gen"] == 1
    assert resilience.buddy_gens()[0] == 1


# ---------------------------------------------------------------------------
# restore planning + the two-gather adoption protocol
# ---------------------------------------------------------------------------

def _seeded_co(n, gen, members=None):
    co = LocalCoordinator(n, timeout_s=30.0)
    members = list(range(n)) if members is None else members
    for h in members:
        assert buddy.send_snapshot(co, h, members, gen,
                                   _arrays(seed=100 + h))
    return co


def test_plan_restore_verdicts():
    # all mailboxes at the expected generation: restorable
    co = _seeded_co(4, 5)
    assert buddy.plan_restore(co, [0, 1, 2, 3], [], [0, 1, 2, 3], 5) \
        is None
    assert buddy.plan_restore(co, [0, 2, 3], [1], [0, 1, 2, 3], 5) \
        is None
    # lost host whose ring buddy is ALSO lost: the replica died with it
    co = _seeded_co(4, 5)
    assert buddy.plan_restore(co, [0, 3], [1, 2], [0, 1, 2, 3], 5) \
        == "buddy_and_host_lost"
    # any mailbox at the wrong generation: stale
    co = _seeded_co(4, 5)
    assert buddy.plan_restore(co, [0, 2, 3], [1], [0, 1, 2, 3], 6) \
        == "buddy_stale"
    # an absent mailbox: missing
    co = _seeded_co(4, 5, members=[0, 1, 2])
    assert buddy.plan_restore(co, [0, 1, 2, 3], [], [0, 1, 2, 3], 5) \
        == "buddy_missing"


class _ScriptedCo(object):
    """agree_plan unit double: scripted gather result, real-ish blobs."""

    def __init__(self, verdicts, gen=1):
        self._verdicts = dict(verdicts)
        blob, _, _ = io_mod.encode_state_blob(_arrays(), gen)
        self._rec = {"gen": gen, "buddy": 1, "blob": blob}

    def get_blob(self, owner, meta_only=False):
        return dict(self._rec)

    def all_gather(self, name, host_id, value=None, timeout_s=None):
        return dict(self._verdicts)


def test_agree_plan_conservative_merge_precedence():
    ok = buddy.agree_plan(_ScriptedCo({0: "ok", 1: "ok"}), 0, "t",
                          [0, 1], [], [0, 1], 1)
    assert ok is None
    # ANY host's doubt falls the pod back...
    got = buddy.agree_plan(_ScriptedCo({0: "ok", 1: "buddy_stale"}),
                           0, "t", [0, 1], [], [0, 1], 1)
    assert got == "buddy_stale"
    # ...and mixed reasons merge under FALLBACK_REASONS precedence so
    # every host records the same label
    got = buddy.agree_plan(
        _ScriptedCo({0: "snapshot_torn", 1: "buddy_missing"}),
        0, "t", [0, 1], [], [0, 1], 1)
    assert got == "buddy_missing"
    got = buddy.agree_plan(
        _ScriptedCo({0: "buddy_stale", 1: "buddy_and_host_lost"}),
        0, "t", [0, 1], [], [0, 1], 1)
    assert got == "buddy_and_host_lost"


def test_restore_agreed_adopts_bitwise():
    co = _seeded_co(2, 4)
    scopes = {h: _DictScope(w=np.zeros((3, 4), np.float32),
                            **{"nested/b": np.zeros((3, 4), np.float32)})
              for h in range(2)}
    out, errs = _run_hosts(
        lambda h: buddy.restore_agreed(co, h, "r", 4, scopes[h]), 2)
    assert not errs
    assert all(ok for ok, _fs in out.values())
    for h in range(2):
        want = _arrays(seed=100 + h)
        for n in want:
            np.testing.assert_array_equal(scopes[h].vars[n], want[n])
    adopts = resilience.events("buddy_adopt")
    assert sorted(e["host"] for e in adopts) == [0, 1]


def test_restore_agreed_torn_blob_nobody_adopts():
    """One host's payload is garbage: decode fails BEFORE any scope
    mutation, the second gather spreads the doubt, and BOTH hosts
    return unrestored — a torn snapshot can never half-restore a pod."""
    co = _seeded_co(2, 4)
    # garble owner 1's payload in BOTH resident mailboxes (its own
    # self-deposit and the buddy replica) so every fetch path sees it
    for at in (0, 1):
        mb = co.mailbox_of(at)
        with mb._lock:
            slot = mb._slots.get(1)
            if slot is not None:
                slot["base"] = dict(slot["base"], npz="!not-base64!")
    scopes = {h: _DictScope(w=np.full((3, 4), -1.0, np.float32))
              for h in range(2)}
    out, errs = _run_hosts(
        lambda h: buddy.restore_agreed(co, h, "r", 4, scopes[h]), 2)
    assert not errs
    assert all(o == (False, None) for o in out.values())
    for h in range(2):   # scopes untouched — including the healthy host
        np.testing.assert_array_equal(
            scopes[h].vars["w"], np.full((3, 4), -1.0, np.float32))
    fails = resilience.events("buddy_decode_fail")
    assert fails and {e["host"] for e in fails} == {1}


def test_fault_mid_restore_nobody_adopts():
    """Satellite: the catalogued ``buddy.restore`` failpoint fires
    between fetch and decode on one host — same no-adoption outcome."""
    co = _seeded_co(2, 2)
    scopes = {h: _DictScope(w=np.zeros((3, 4), np.float32))
              for h in range(2)}
    faultinject.arm(["buddy.restore:raise@1^1"])
    try:
        out, errs = _run_hosts(
            lambda h: buddy.restore_agreed(co, h, "r", 2, scopes[h]), 2)
    finally:
        faultinject.disarm()
    assert not errs
    assert all(o == (False, None) for o in out.values())
    fired = [e for e in resilience.events("failpoint")
             if e["site"] == "buddy.restore"]
    assert fired and fired[0]["host"] == "1"
    assert {e["host"] for e in resilience.events("buddy_decode_fail")} \
        == {1}


def test_file_coordinator_degrades_to_buddy_missing(tmp_path):
    """FileCoordinator's mailbox store is per-process: peers never see
    each other's puts, so every restore plan reports buddy_missing and
    the pod takes the disk rewind — the documented degradation."""
    root = str(tmp_path / "fc")
    cos = [FileCoordinator(root, 2, timeout_s=5.0, poll_s=0.002)
           for _ in range(2)]
    for h in range(2):
        assert buddy.send_snapshot(cos[h], h, [0, 1], 1,
                                   _arrays(seed=h))
    assert buddy.plan_restore(cos[0], [0, 1], [], [0, 1], 1) \
        == "buddy_missing"


# ---------------------------------------------------------------------------
# p2p mailboxes: single-generation residency + typed delta protocol
# ---------------------------------------------------------------------------

def _full_payload(arrays, gen, reset=False):
    blob, _, _ = io_mod.encode_state_blob(arrays, gen, compress="zlib")
    p = {"kind": "full", "gen": gen,
         "digest": io_mod.state_digest(arrays), "blob": blob}
    if reset:
        p["reset"] = True
    return p


def _delta_payload(changed, gen, prev_gen, prev_digest, full_arrays,
                   removed=()):
    blob, _, _ = io_mod.encode_state_blob(changed, gen, compress="zlib")
    return {"kind": "delta", "gen": gen, "prev_gen": prev_gen,
            "prev_digest": prev_digest,
            "digest": io_mod.state_digest(full_arrays),
            "removed": list(removed), "blob": blob}


def test_mailbox_one_generation_resident_fence_and_reset():
    """A mailbox slot holds exactly ONE generation: a full deposit
    replaces wholesale, a rewind is a typed refusal (reset bypasses),
    and resident bytes track the single resident payload — never an
    accumulation of generations."""
    mb = buddy.BuddyMailbox(host_id=0)
    a3, a5 = _arrays(seed=3), _arrays(seed=5)
    ack = mb.deposit(7, _full_payload(a3, 3))
    assert ack["ok"] and ack["gen"] == 3 and ack["chain_len"] == 0
    ack = mb.deposit(7, _full_payload(a5, 5))
    assert ack["ok"] and ack["gen"] == 5
    # ONE generation resident: gen-3 is gone, resident == gen-5 bytes
    assert mb.meta(7)["gen"] == 5
    assert mb.resident_bytes() == ack["nbytes"]
    got, step, _ = io_mod.decode_state_blob(mb.reconstruct(7)["blob"])
    assert step == 5
    for n in a5:
        np.testing.assert_array_equal(got[n], a5[n])
    # rewind refused (typed, not raised) ...
    ref = mb.deposit(7, _full_payload(a3, 2))
    assert ref == {"ok": False, "refused": "gen_rewind", "gen": 5}
    assert mb.meta(7)["gen"] == 5
    # ... unless it is a reset re-seed
    ack = mb.deposit(7, _full_payload(a3, 2, reset=True))
    assert ack["ok"] and mb.meta(7)["gen"] == 2
    # the per-host resident gauge follows (host_id was given)
    assert resilience.buddy_resident()["0"] == mb.resident_bytes()
    mb.drop(7)
    assert mb.meta(7) is None and mb.resident_bytes() == 0


def test_mailbox_delta_refusals_are_typed():
    """Every way a delta deposit can be unappliable is a TYPED refusal
    the sender converts into one forced full — no exceptions, no
    partial slot mutation."""
    mb = buddy.BuddyMailbox(host_id=1, max_chain=2)
    base = _arrays(seed=0)
    # delta into an empty slot: no base to chain onto
    ref = mb.deposit(4, _delta_payload({"w": base["w"]}, 1, 0, "x", base))
    assert ref["ok"] is False and ref["refused"] == "delta_chain_broken"
    ack = mb.deposit(4, _full_payload(base, 1))
    assert ack["ok"]
    d1 = dict(base, w=base["w"] + 1)
    # wrong prev_gen: the sender's chain state diverged from the slot
    ref = mb.deposit(4, _delta_payload({"w": d1["w"]}, 2, 0,
                                       ack["digest"], d1))
    assert ref == {"ok": False, "refused": "delta_chain_broken", "gen": 1}
    # right prev_gen but wrong prev_digest: content diverged
    ref = mb.deposit(4, _delta_payload({"w": d1["w"]}, 2, 1,
                                       "not-the-digest", d1))
    assert ref == {"ok": False, "refused": "digest_mismatch", "gen": 1}
    # a non-advancing delta generation is a rewind
    ref = mb.deposit(4, _delta_payload({"w": d1["w"]}, 1, 1,
                                       ack["digest"], d1))
    assert ref == {"ok": False, "refused": "gen_rewind", "gen": 1}
    # a valid chain applies ... up to max_chain, then refuses typed
    ack1 = mb.deposit(4, _delta_payload({"w": d1["w"]}, 2, 1,
                                        ack["digest"], d1))
    assert ack1["ok"] and ack1["chain_len"] == 1
    d2 = dict(d1, w=d1["w"] + 1)
    ack2 = mb.deposit(4, _delta_payload({"w": d2["w"]}, 3, 2,
                                        ack1["digest"], d2))
    assert ack2["ok"] and ack2["chain_len"] == 2
    d3 = dict(d2, w=d2["w"] + 1)
    ref = mb.deposit(4, _delta_payload({"w": d3["w"]}, 4, 3,
                                       ack2["digest"], d3))
    assert ref["ok"] is False and ref["refused"] == "delta_chain_broken"
    # the capped slot still reconstructs its committed generation
    got, step, _ = io_mod.decode_state_blob(mb.reconstruct(4)["blob"])
    assert step == 3
    np.testing.assert_array_equal(got["w"], d2["w"])


@pytest.mark.parametrize("static,churning", [
    ((64, 32), {"churn/w": (3, 4)}),
    # one large static table under small heads that churn every window:
    # what most of a real scope looks like
    ((1024, 256), {"head/w%d" % i: (64, 64) for i in range(4)}),
], ids=["one_leaf", "churn_skewed"])
def test_delta_sends_skip_unchanged_leaves_and_rebase(static, churning):
    """Sender-side delta protocol over LocalCoordinator: unchanged
    leaves never move again (delta wire under half the full wire on a
    static-heavy scope), the chain re-bases to a forced full every
    rebase_every sends, and the restore after a re-base boundary is
    bitwise."""
    co = LocalCoordinator(2, timeout_s=5.0)
    tracker = buddy.DeltaTracker(rebase_every=2)
    rng = np.random.RandomState(0)

    def churn():
        return {n: rng.randn(*shape).astype(np.float32)
                for n, shape in churning.items()}
    scope = dict(churn(), **{
        "static/table": rng.randn(*static).astype(np.float32)})
    assert buddy.send_snapshot(co, 0, [0, 1], 0, scope, tracker=tracker)
    full_wire = tracker.full_wire
    assert tracker.chain_len == 0 and full_wire
    for gen in (1, 2):   # deltas: only the churning leaves move
        scope = dict(scope, **churn())
        assert buddy.send_snapshot(co, 0, [0, 1], gen, scope,
                                   tracker=tracker)
        assert tracker.chain_len == gen
        assert resilience.buddy_delta_ratio() < 0.5
    # the next send finds the chain at rebase_every: forced full, the
    # buddy slot's chain collapses
    scope = dict(scope, **churn())
    assert buddy.send_snapshot(co, 0, [0, 1], 3, scope, tracker=tracker)
    assert tracker.chain_len == 0
    assert resilience.buddy_delta_ratio() == 1.0
    assert co.mailbox_of(1).meta(0) \
        == dict(co.mailbox_of(0).meta(0))   # both replicas identical
    assert co.mailbox_of(1).meta(0)["chain_len"] == 0
    # post-re-base restore is bitwise
    got, _ = buddy.fetch_and_decode(co, 0, 3)
    for n in scope:
        np.testing.assert_array_equal(got[n], scope[n])
    # metadata row tracks the re-based generation
    assert co.buddy_meta(0)["gen"] == 3


def test_fault_mid_p2p_send_meta_not_advanced_typed():
    """Twin for the catalogued ``buddy.p2p_send`` failpoint: the
    stream to the buddy tears AFTER the local deposit — ack-before-
    commit keeps the metadata row at the previous generation, so the
    torn generation can never be elected and the next restore plan is
    the TYPED buddy_stale disk fallback, not a wedge."""
    co = LocalCoordinator(2, timeout_s=5.0)
    gen0, gen1 = _arrays(seed=20), _arrays(seed=21)
    assert buddy.send_snapshot(co, 0, [0, 1], 0, gen0)
    assert buddy.send_snapshot(co, 1, [0, 1], 0, _arrays(seed=29))
    faultinject.arm(["buddy.p2p_send:raise@1^0"])
    try:
        assert not buddy.send_snapshot(co, 0, [0, 1], 1, gen1)
    finally:
        faultinject.disarm()
    fails = resilience.events("buddy_send_fail")
    assert fails and fails[-1]["host"] == 0 \
        and fails[-1]["error"] == "ConnectionError"
    # metadata never advanced: gen 0 is still the committed truth
    assert co.buddy_meta(0)["gen"] == 0
    # ... so planning a restore at the torn gen 1 is typed stale
    assert buddy.plan_restore(co, [1], [0], [0, 1], 1) == "buddy_stale"
    # and gen 0 itself still restores bitwise from the buddy replica
    got, _ = buddy.fetch_and_decode(co, 0, 0)
    for n in gen0:
        np.testing.assert_array_equal(got[n], gen0[n])
    fired = [e for e in resilience.events("failpoint")
             if e["site"] == "buddy.p2p_send"]
    assert fired and fired[0]["host"] == "0"


def test_fault_mid_p2p_fetch_nobody_adopts_typed():
    """Twin for the catalogued ``buddy.p2p_fetch`` failpoint: the
    host-to-host pull tears mid-stream during an agreed restore — the
    decode gather spreads the doubt, nobody adopts, and the caller
    takes the typed snapshot_torn disk rewind (never a wedge)."""
    co = _seeded_co(2, 2)
    # host 0 restarted: its local replica is gone, forcing the p2p hop
    co.mailbox_of(0).clear()
    scopes = {h: _DictScope(w=np.full((3, 4), -1.0, np.float32))
              for h in range(2)}
    faultinject.arm(["buddy.p2p_fetch:raise@1^0"])
    try:
        out, errs = _run_hosts(
            lambda h: buddy.restore_agreed(co, h, "r", 2, scopes[h]), 2)
    finally:
        faultinject.disarm()
    assert not errs
    assert all(o == (False, None) for o in out.values())
    for h in range(2):   # nobody half-restored
        np.testing.assert_array_equal(
            scopes[h].vars["w"], np.full((3, 4), -1.0, np.float32))
    assert {e["host"] for e in resilience.events("buddy_decode_fail")} \
        == {0}
    fired = [e for e in resilience.events("failpoint")
             if e["site"] == "buddy.p2p_fetch"]
    assert fired and fired[0]["host"] == "0"
    # disarmed, the same p2p pull succeeds bitwise (typed ≠ terminal)
    got, _ = buddy.fetch_and_decode(co, 0, 2)
    want = _arrays(seed=100)
    for n in want:
        np.testing.assert_array_equal(got[n], want[n])
    assert resilience.buddy_fetch_ms() is not None


def test_fault_delta_apply_reconstruct_torn_typed():
    """Twin for the catalogued ``buddy.delta_apply`` failpoint: a
    fault while replaying a chain link makes reconstruct raise, the
    fetch surfaces it as a decode failure and the pod takes the typed
    no-adoption path — a torn chain can never half-restore."""
    co = LocalCoordinator(2, timeout_s=5.0)
    tracker = buddy.DeltaTracker(rebase_every=8)
    arrays = _arrays(seed=40)
    assert buddy.send_snapshot(co, 0, [0, 1], 0, arrays,
                               tracker=tracker)
    arrays = dict(arrays, w=arrays["w"] + 1)
    assert buddy.send_snapshot(co, 0, [0, 1], 1, arrays,
                               tracker=tracker)
    assert co.mailbox_of(1).meta(0)["chain_len"] == 1
    faultinject.arm(["buddy.delta_apply:raise@1+"])
    try:
        with pytest.raises(Exception):
            buddy.fetch_and_decode(co, 0, 1)
    finally:
        faultinject.disarm()
    # disarmed, the same chain reconstructs bitwise
    got, _ = buddy.fetch_and_decode(co, 0, 1)
    for n in arrays:
        np.testing.assert_array_equal(got[n], arrays[n])


def test_delta_chain_corruption_fails_digest_typed():
    """A corrupted stored chain link reconstructs to the WRONG state:
    the slot's end-to-end digest catches it and the fetch raises — the
    typed snapshot_torn input, never a silent wrong-weights adopt."""
    co = LocalCoordinator(2, timeout_s=5.0)
    tracker = buddy.DeltaTracker(rebase_every=8)
    arrays = _arrays(seed=50)
    assert buddy.send_snapshot(co, 0, [0, 1], 0, arrays,
                               tracker=tracker)
    arrays = dict(arrays, w=arrays["w"] + 1)
    assert buddy.send_snapshot(co, 0, [0, 1], 1, arrays,
                               tracker=tracker)
    # tamper the delta link's payload in BOTH resident mailboxes with a
    # VALID encoding of different content — only the digest can tell
    evil, _, _ = io_mod.encode_state_blob(
        {"w": np.zeros((3, 4), np.float32)}, 1, compress="zlib")
    for at in (0, 1):
        mb = co.mailbox_of(at)
        with mb._lock:
            mb._slots[0]["chain"][0]["blob"] = evil
    with pytest.raises(ValueError, match="digest"):
        buddy.fetch_and_decode(co, 0, 1)


def test_double_loss_typed_from_recorded_buddy():
    """Owner AND its META-recorded buddy both lost: even when the
    current ring would assign a different buddy, the replica lived in
    the RECORDED buddy's RAM — plan says buddy_and_host_lost."""
    co = _seeded_co(3, 4)   # ring 0->1->2->0, meta records buddy(1)=2
    # hosts 1 and 2 die together: host 1's replica was in host 2's RAM
    assert buddy.plan_restore(co, [0], [1, 2], [0, 1, 2], 4) \
        == "buddy_and_host_lost"
    # the meta-recorded check also catches a STALE ring: host 1's last
    # committed send pre-dated a membership change, so the current ring
    # says buddy(1)=0 but the payload sits in dead host 2's mailbox
    assert buddy.plan_restore(co, [0], [1, 2], [0, 1, 2, 3], 4) \
        in ("buddy_and_host_lost",)


def test_restore_parity_delta_full_legacy_bitwise():
    """Acceptance: the p2p delta-chain restore, the p2p full-snapshot
    restore and the legacy coordinator-mailbox restore all reconstruct
    BITWISE-identical state from the same send history."""
    rng = np.random.RandomState(3)
    history = []
    state = {"static/emb": rng.randn(32, 16).astype(np.float32),
             "churn/w": rng.randn(3, 4).astype(np.float32)}
    for gen in range(4):
        state = dict(state, **{"churn/w": rng.randn(3, 4)
                               .astype(np.float32)})
        history.append((gen, state))
    co_d = LocalCoordinator(2, timeout_s=5.0)   # p2p + deltas
    co_f = LocalCoordinator(2, timeout_s=5.0)   # p2p, full every time
    co_l = LocalCoordinator(2, timeout_s=5.0)   # legacy put_blob
    tracker = buddy.DeltaTracker(rebase_every=8)
    peer = _arrays(seed=90)   # host 1 participates so plans can pass
    for gen, st in history:
        assert buddy.send_snapshot(co_d, 0, [0, 1], gen, st,
                                   tracker=tracker)
        assert buddy.send_snapshot(co_f, 0, [0, 1], gen, st)
        assert buddy.send_snapshot(co_l, 0, [0, 1], gen, st, p2p=False)
        for co, p2p in ((co_d, True), (co_f, True), (co_l, False)):
            assert buddy.send_snapshot(co, 1, [0, 1], gen, peer,
                                       p2p=p2p)
    assert co_d.mailbox_of(1).meta(0)["chain_len"] == 3
    final = history[-1][1]
    got_d, _ = buddy.fetch_and_decode(co_d, 0, 3)
    got_f, _ = buddy.fetch_and_decode(co_f, 0, 3)
    got_l, _ = buddy.fetch_and_decode(co_l, 0, 3, p2p=False)
    for n in final:
        np.testing.assert_array_equal(got_d[n], final[n])
        np.testing.assert_array_equal(got_f[n], final[n])
        np.testing.assert_array_equal(got_l[n], final[n])
    # and all three plans agree the restore is possible
    for co, p2p in ((co_d, True), (co_f, True), (co_l, False)):
        assert buddy.plan_restore(co, [1], [0], [0, 1], 3, p2p=p2p) \
            is None


# ---------------------------------------------------------------------------
# p2p over sockets: MailboxServer endpoints + the metadata-only plane
# ---------------------------------------------------------------------------

def test_mailbox_server_wire_roundtrip():
    """The MailboxServer speaks the newline-JSON wire: deposit, fetch,
    status and the typed miss — and a dead endpoint raises
    ConnectionError (the sender's swallow-into-event input), never
    hangs."""
    arrays = _arrays(seed=60)
    with MailboxServer(buddy.BuddyMailbox(host_id=3)) as srv:
        ack = mailbox_request(srv.address, {
            "cmd": "mb_deposit", "owner": 2,
            "payload": _full_payload(arrays, 5)})
        assert ack["ok"] and ack["gen"] == 5
        rec = mailbox_request(srv.address, {"cmd": "mb_fetch",
                                            "owner": 2})
        got, step, _ = io_mod.decode_state_blob(rec["blob"])
        assert step == 5
        for n in arrays:
            np.testing.assert_array_equal(got[n], arrays[n])
        assert mailbox_request(srv.address,
                               {"cmd": "mb_fetch", "owner": 9}) \
            == {"miss": True}
        st = mailbox_request(srv.address, {"cmd": "mb_status"})
        assert st["owners"]["2"]["gen"] == 5
        assert st["resident_bytes"] == ack["nbytes"]
        addr = srv.address
    with pytest.raises(ConnectionError):
        mailbox_request(addr, {"cmd": "mb_status"}, timeout_s=0.5)


def test_socket_p2p_coordinator_holds_metadata_only():
    """THE tentpole invariant over real sockets: snapshot payloads
    live only in the hosts' MailboxServer endpoints; the CoordServer
    keeps a metadata table whose resident footprint is O(bytes of
    JSON), counter-asserted against the gauge — and a host-to-host
    pull after a restart restores bitwise."""
    with contextlib.ExitStack() as stack:
        srv = CoordServer(2, hb_deadline_s=30.0).start()
        stack.callback(srv.close)
        cos = _socket_pod(stack, srv.address, 2)
        refs = {h: _arrays(seed=70 + h) for h in range(2)}
        for h in range(2):
            assert buddy.send_snapshot(cos[h], h, [0, 1], 1, refs[h])
        with srv.state.lock:
            # NO payloads on the coordination plane — metadata only
            assert srv.state.blobs == {}
            meta = dict(srv.state.buddy_meta)
            addrs = dict(srv.state.mailbox_addrs)
        assert set(meta) == {0, 1} and set(addrs) == {0, 1}
        assert meta[0]["buddy"] == 1 and meta[1]["buddy"] == 0
        assert meta[0]["nbytes"] > 0 and meta[0]["digest"]
        # the coordinator's resident gauge is metadata-sized: far
        # below ONE snapshot payload, under the probe's strict bound
        resident = resilience.buddy_resident()["coord"]
        assert 0 < resident < min(m["nbytes"] for m in meta.values())
        from tools.serving_probe import BUDDY_COORD_RESIDENT_BOUND
        assert resident < BUDDY_COORD_RESIDENT_BOUND
        # host 0 "restarts": local mailbox replica gone — the restore
        # pulls host-to-host from host 1's endpoint, bitwise
        cos[0].mailbox_of(0).clear()
        got, _ = buddy.fetch_and_decode(cos[0], 0, 1)
        for n in refs[0]:
            np.testing.assert_array_equal(got[n], refs[0][n])
        assert resilience.buddy_fetch_ms() is not None
        # both hosts' mailbox endpoints carry exactly one replica each
        # now (host 0's cleared slot is only in host 1's RAM)
        assert cos[1].mailbox_of(1).owners() == [0, 1]


def test_put_blob_ceiling_is_a_named_error():
    """Satellite bugfix: legacy put_blob/get_blob stay for
    compatibility but the coordinator now enforces blob_max_bytes —
    an oversized legacy payload is the NAMED BlobTooLargeError, in
    process and across the wire, and the mailbox keeps its previous
    committed generation."""
    # in-process: the ceiling is opt-in (None = unbounded, compat)
    co = LocalCoordinator(2, timeout_s=5.0)
    big, _, _ = io_mod.encode_state_blob(
        {"w": np.zeros((64, 64), np.float32)}, 1, compress=None)
    co.put_blob(0, 1, 1, big)          # unbounded: fine
    co.blob_max_bytes = 1024
    with pytest.raises(BlobTooLargeError, match="blob_max_bytes"):
        co.put_blob(0, 2, 1, big)
    assert co.get_blob(0, meta_only=True)["gen"] == 1   # not torn
    # over the wire: CoordServer defaults the ceiling ON (64 MiB);
    # shrink it to prove the typed path end to end
    with contextlib.ExitStack() as stack:
        srv = CoordServer(2, hb_deadline_s=30.0,
                          blob_max_bytes=1024).start()
        stack.callback(srv.close)
        cos = _socket_pod(stack, srv.address, 2)
        small, _, _ = io_mod.encode_state_blob(_arrays(), 1)
        cos[0].put_blob(0, 1, 1, small)
        with pytest.raises(BlobTooLargeError, match="blob_max_bytes"):
            cos[0].put_blob(0, 2, 1, big)
        assert cos[1].get_blob(0, meta_only=True)["gen"] == 1


# ---------------------------------------------------------------------------
# pod integration: PodResilientTrainer with the buddy tier
# ---------------------------------------------------------------------------

def _toy_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(x, size=1, param_attr=pt.ParamAttr(name="pod_w"),
                         bias_attr=pt.ParamAttr(name="pod_b"))
        loss = layers.reduce_mean(layers.square(pred - y))
        optimizer.Adam(0.05).minimize(loss)
    return main, startup, loss


def _toy_feeds(n, seed=0, batch=4):
    rng = np.random.RandomState(seed)
    w = rng.randn(4, 1).astype(np.float32)
    out = []
    for _ in range(n):
        xv = rng.randn(batch, 4).astype(np.float32)
        out.append({"x": xv, "y": (xv @ w).astype(np.float32)})
    return out


def _make_pod(tmp_path, tag, n_hosts=3, checkpoint_every=3, **pod_kw):
    main, startup, loss = _toy_program()
    trainers = []
    for h in range(n_hosts):
        sc, exe = Scope(), pt.Executor()
        with scope_guard(sc):
            exe.run(startup)
        trainers.append(ResilientTrainer(
            exe, main, str(tmp_path / tag / ("h%d" % h)),
            fetch_list=[loss], checkpoint_every=checkpoint_every,
            scope=sc, retry_policy=_fast_policy()))
    pod = PodResilientTrainer(
        trainers, LocalCoordinator(n_hosts, timeout_s=POD_TIMEOUT_S),
        **pod_kw)
    return pod, trainers, loss


def _pod_params(trainers, name="pod_w"):
    return [t._scope.get_numpy(name).copy() for t in trainers]


def test_pod_preempt_buddy_restores_warm_bitwise(tmp_path):
    """THE buddy acceptance, in-process: a preempt one step past the
    window-4 boundary restores from the BUDDY snapshots at step 4 —
    not the step-3 disk checkpoint — losing at most the open window,
    with no scrub, no disk election, and params/fetches bitwise equal
    to the uninterrupted reference."""
    ref_pod, ref_trainers, _ = _make_pod(tmp_path, "ref")
    feeds = _toy_feeds(9)
    ref_fetches = ref_pod.run(feeds)
    ref_w = _pod_params(ref_trainers)
    resilience.clear_events()

    chaos_pod, chaos_trainers, _ = _make_pod(tmp_path, "chaos")
    # 3 hosts x windows of 1 step: fires 13..15 are window 5, so the
    # fault strikes with the gen-4 snapshots already acked
    with resilience.inject("step:preempt@14"):
        got_fetches = chaos_pod.run(feeds)

    for a, b in zip(ref_w, _pod_params(chaos_trainers)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(ref_fetches),
                                  np.asarray(got_fetches))
    # every host restored WARM from the buddy tier at the last boundary
    restores = resilience.events("pod_restore")
    assert sorted(e["host"] for e in restores) == [0, 1, 2]
    assert {e["step"] for e in restores} == {4}
    br = resilience.events("buddy_restore")
    assert sorted(e["host"] for e in br) == [0, 1, 2]
    assert {e["outcome"] for e in br} == {"ok"}
    assert {e["step"] for e in br} == {4}
    assert {e["step"] for e in resilience.events("consensus")} == {4}
    # the disk machinery never ran: no scrub, no election
    assert not resilience.events("scrub")
    # metrics contract: restore outcomes + per-host generation gauges
    m = resilience.metrics()
    br_counts = {c["labels"]["outcome"]: c["value"]
                 for c in m["counters"]
                 if c["name"].endswith("_buddy_restore_total")}
    assert br_counts == {"ok": 3}
    gens = {g["labels"]["host"]: g["value"] for g in m["gauges"]
            if g["name"].endswith("_buddy_generation")}
    assert set(gens) == {"0", "1", "2"}
    assert set(gens.values()) == {float(len(feeds))}


def test_pod_stale_mailbox_falls_back_to_disk_typed(tmp_path):
    """Satellite: one host's sends tear from window 2 on (armed
    buddy.send failpoint) — at the next fault its mailbox generation
    is behind, the pod agrees ``buddy_stale`` and takes the DISK
    rewind to the step-3 checkpoint, still bitwise-correct."""
    ref_pod, ref_trainers, _ = _make_pod(tmp_path, "ref", n_hosts=2)
    feeds = _toy_feeds(6)
    ref_fetches = ref_pod.run(feeds)
    ref_w = _pod_params(ref_trainers)
    resilience.clear_events()

    chaos_pod, chaos_trainers, _ = _make_pod(tmp_path, "chaos",
                                             n_hosts=2)
    # host 0's sends fail from its 3rd visit on (seed=1, gen1=2, ...):
    # its mailbox freezes at gen 1 while host 1 keeps publishing
    faultinject.arm(["buddy.send:raise=ConnectionError@3+^0"])
    try:
        # 2 hosts x 1-step windows: fires 9,10 are window 5 (step 4)
        with resilience.inject("step:preempt@9"):
            got_fetches = chaos_pod.run(feeds)
    finally:
        faultinject.disarm()

    for a, b in zip(ref_w, _pod_params(chaos_trainers)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(ref_fetches),
                                  np.asarray(got_fetches))
    # the typed reason label, agreed by every host
    br = resilience.events("buddy_restore")
    assert sorted(e["host"] for e in br) == [0, 1]
    assert {e["outcome"] for e in br} == {"buddy_stale"}
    assert resilience.events("buddy_send_fail")
    # and the fallback really was the disk rewind to step 3
    assert {e["step"] for e in resilience.events("pod_restore")} == {3}
    assert resilience.events("scrub")


def test_pod_torn_snapshot_falls_back_to_disk_typed(tmp_path):
    """Satellite: the ``buddy.restore`` failpoint tears one host's
    decode mid-restore — the pod agrees ``snapshot_torn``, nobody
    adopts, and the disk rewind (baseline step 0 here) produces the
    bitwise-correct run."""
    ref_pod, ref_trainers, _ = _make_pod(tmp_path, "ref", n_hosts=2)
    feeds = _toy_feeds(6)
    ref_fetches = ref_pod.run(feeds)
    ref_w = _pod_params(ref_trainers)
    resilience.clear_events()

    chaos_pod, chaos_trainers, _ = _make_pod(tmp_path, "chaos",
                                             n_hosts=2)
    faultinject.arm(["buddy.restore:raise@1^0"])
    try:
        # fires 5,6 are window 3: fault at step 2, before any periodic
        # checkpoint — the disk fallback lands on baseline step 0
        with resilience.inject("step:preempt@5"):
            got_fetches = chaos_pod.run(feeds)
    finally:
        faultinject.disarm()

    for a, b in zip(ref_w, _pod_params(chaos_trainers)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(ref_fetches),
                                  np.asarray(got_fetches))
    br = resilience.events("buddy_restore")
    assert sorted(e["host"] for e in br) == [0, 1]
    assert {e["outcome"] for e in br} == {"snapshot_torn"}
    assert {e["host"] for e in resilience.events("buddy_decode_fail")} \
        == {0}
    assert {e["step"] for e in resilience.events("pod_restore")} == {0}


def test_pod_buddy_off_is_pure_disk(tmp_path):
    """buddy=False: no sends, no mailboxes, no buddy events — the
    historical disk-only pod, byte for byte."""
    pod, trainers, _ = _make_pod(tmp_path, "off", n_hosts=2,
                                 buddy=False)
    feeds = _toy_feeds(6)
    with resilience.inject("step:preempt@5"):
        pod.run(feeds)
    assert not resilience.events("buddy_restore")
    assert not resilience.events("buddy_send_fail")
    assert resilience.buddy_gens() == {}
    assert pod._coordinator.get_blob(0) is None
    assert {e["step"] for e in resilience.events("pod_restore")} == {0}


# ---------------------------------------------------------------------------
# retention GC vs scrub classification (the satellite bugfix)
# ---------------------------------------------------------------------------

def test_retention_gc_serialized_against_scrub(tmp_path, monkeypatch):
    """REGRESSION: an async-commit retention GC racing a restore
    election's scrub could collect the very step the scrub just called
    valid (the buddy tier's disk fallback elects from that report).
    _RETENTION_LOCK must hold the GC off until classification ends."""
    root = str(tmp_path / "ck")
    for s in (1, 2, 3):
        os.makedirs(os.path.join(root, "step_%d" % s))
    started, release = threading.Event(), threading.Event()
    state = {"blocked": False}

    def slow_classify(dirname, step_dir):
        if not state["blocked"]:       # first call: park mid-scrub
            state["blocked"] = True
            started.set()
            assert release.wait(timeout=30.0)
        return "valid", None

    monkeypatch.setattr(io_mod, "_classify_step_dir", slow_classify)
    report = {}
    scrubber = threading.Thread(
        target=lambda: report.update(io_mod.scrub_checkpoint(root)))
    scrubber.start()
    assert started.wait(timeout=30.0)
    pruner = threading.Thread(
        target=lambda: io_mod._prune_step_dirs(root, 1))
    pruner.start()
    time.sleep(0.3)
    # the GC is parked on the lock: nothing was deleted mid-scrub
    assert pruner.is_alive()
    assert sorted(os.listdir(root)) == ["step_1", "step_2", "step_3"]
    release.set()
    scrubber.join(timeout=30.0)
    pruner.join(timeout=30.0)
    assert not scrubber.is_alive() and not pruner.is_alive()
    # the scrub's report was classified over a stable directory...
    assert report["valid_steps"] == [1, 2, 3]
    # ...and the GC then applied retention normally (newest valid kept)
    assert sorted(d for d in os.listdir(root)
                  if d.startswith("step_")) == ["step_3"]


def test_probe_folds_buddy_group_and_strict_gen_divergence():
    """tools/serving_probe.py: the three buddy series fold under one
    "buddy" group (the snapshot byte pairs claimed BEFORE the generic
    *_bytes_total fold), and buddy_generation_flags trips only when
    hosts' generation gauges diverge by more than one window — the
    straddle a scrape landing mid-round legitimately sees stays
    green."""
    import sys
    resilience.clear_bytes()
    resilience.clear_buddy_gens()
    resilience.record_bytes("buddy_snapshot", 4096, 512)
    resilience.record_event("buddy_restore", outcome="ok")
    resilience.record_buddy_gen(0, 7)
    resilience.record_buddy_gen(1, 6)  # one-window straddle: legal
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import serving_probe
    finally:
        sys.path.pop(0)
    with resilience.serve_metrics(port=0) as srv:
        report = serving_probe.scrape_metrics(srv.url)
    assert report["buddy"] == {
        "buddy_snapshot_bytes_total/raw": 4096.0,
        "buddy_snapshot_bytes_total/wire": 512.0,
        "buddy_restore_total/ok": 1.0,
        "buddy_generation/host0": 7.0,
        "buddy_generation/host1": 6.0}
    # claimed before the generic fold: nothing buddy leaks into "bytes"
    assert not any(k.startswith("buddy")
                   for k in report.get("bytes", {}))
    assert serving_probe.buddy_generation_flags(report) == []
    # host 1 falls TWO windows behind — its buddy's mailbox is going
    # stale, and the next loss of host 1 is a full disk rewind
    resilience.record_buddy_gen(0, 8)
    with resilience.serve_metrics(port=0) as srv:
        report = serving_probe.scrape_metrics(srv.url)
    flags = serving_probe.buddy_generation_flags(report)
    assert len(flags) == 1 and "more than one window" in flags[0]


def test_probe_strict_coordinator_resident_bound():
    """tools/serving_probe.py: the p2p-tier gauges
    (buddy_resident_bytes{host=}, buddy_delta_ratio,
    buddy_p2p_fetch_ms) fold into the "buddy" group, and
    buddy_resident_flags trips ONLY when the COORDINATOR's resident
    gauge exceeds the metadata-sized bound — payload-sized mailboxes
    on the hosts themselves are exactly what the tier wants."""
    import sys
    resilience.clear_bytes()
    resilience.clear_buddy_gens()
    resilience.record_buddy_resident(0, 5 * 1024 * 1024)  # host RAM: fine
    resilience.record_buddy_resident("coord", 512)        # metadata: fine
    resilience.record_buddy_delta_ratio(0.07)
    resilience.record_buddy_fetch_ms(1.25)
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import serving_probe
    finally:
        sys.path.pop(0)
    with resilience.serve_metrics(port=0) as srv:
        report = serving_probe.scrape_metrics(srv.url)
    assert report["buddy"]["buddy_resident_bytes/host0"] \
        == 5 * 1024 * 1024.0
    assert report["buddy"]["buddy_resident_bytes/hostcoord"] == 512.0
    assert report["buddy"]["buddy_delta_ratio"] == 0.07
    assert report["buddy"]["buddy_p2p_fetch_ms"] == 1.25
    assert serving_probe.buddy_resident_flags(report) == []
    # a payload-sized COORDINATOR residency trips the strict flag: the
    # memory ceiling the p2p mailboxes lifted is back
    resilience.record_buddy_resident("coord", 5 * 1024 * 1024)
    with resilience.serve_metrics(port=0) as srv:
        report = serving_probe.scrape_metrics(srv.url)
    flags = serving_probe.buddy_resident_flags(report)
    assert len(flags) == 1 and "metadata bound" in flags[0] \
        and "coord" in flags[0]
