"""Swarm substrate tests: many real peers on loopback sockets.

The strategy SURVEY.md §4 prescribes (and hivemind upstream uses): launch N
DHT nodes in one process on 127.0.0.1, form a real swarm through real
sockets, and produce fault cases by killing peers mid-protocol.
"""

import time

import pytest
from pydantic import BaseModel, StrictFloat, StrictInt, conint

from dalle_tpu.swarm import (DHT, Identity, SchemaValidator,
                             SignatureValidator, get_dht_time, strip_owner)


def make_swarm(n, validators=lambda ident: [], **kwargs):
    """n bootstrapped peers; caller must shutdown (or use fixture)."""
    nodes = []
    for _ in range(n):
        ident = Identity.generate()
        peers = [nodes[0].visible_address] if nodes else []
        nodes.append(DHT(initial_peers=peers, identity=ident,
                         record_validators=validators(ident),
                         rpc_timeout=2.0, **kwargs))
    return nodes


@pytest.fixture
def swarm5():
    nodes = make_swarm(5)
    yield nodes
    for n in nodes:
        n.shutdown()


class TestDHT:
    def test_store_get_across_peers(self, swarm5):
        exp = get_dht_time() + 60
        assert swarm5[1].store("progress", "peerA", {"samples": 17}, exp)
        got = swarm5[4].get("progress")
        assert got is not None
        assert got[b"peerA"].value == {"samples": 17}
        assert got[b"peerA"].expiration_time == pytest.approx(exp)

    def test_subkeys_merge_from_different_writers(self, swarm5):
        exp = get_dht_time() + 60
        swarm5[0].store("metrics", "a", 1, exp)
        swarm5[2].store("metrics", "b", 2, exp)
        got = swarm5[3].get("metrics")
        assert got is not None and set(got) == {b"a", b"b"}

    def test_latest_expiration_wins(self, swarm5):
        t = get_dht_time()
        swarm5[0].store("k", "s", "old", t + 30)
        swarm5[1].store("k", "s", "new", t + 60)
        got = swarm5[2].get("k")
        assert got[b"s"].value == "new"

    def test_expired_records_vanish(self, swarm5):
        swarm5[0].store("ephemeral", "s", 1, get_dht_time() + 0.5)
        assert swarm5[1].get("ephemeral") is not None
        time.sleep(0.8)
        assert swarm5[2].get("ephemeral") is None

    def test_missing_key_returns_none(self, swarm5):
        assert swarm5[0].get("no-such-key") is None

    def test_peer_death_does_not_break_lookup(self):
        nodes = make_swarm(6)
        try:
            exp = get_dht_time() + 60
            nodes[1].store("sturdy", "s", "v", exp)
            nodes[2].shutdown()  # a volunteer leaves ungracefully
            got = nodes[5].get("sturdy")
            assert got is not None and got[b"s"].value == "v"
        finally:
            for i, n in enumerate(nodes):
                if i != 2:
                    n.shutdown()

    def test_client_mode_can_read_and_write(self):
        nodes = make_swarm(3)
        client = DHT(initial_peers=[nodes[0].visible_address],
                     client_mode=True, rpc_timeout=2.0)
        try:
            assert client.port == 0
            exp = get_dht_time() + 60
            assert client.store("from-client", "c", 42, exp)
            assert nodes[2].get("from-client")[b"c"].value == 42
            # and other peers never route to the client
            for n in nodes:
                assert client.peer_id not in n.peers()
        finally:
            client.shutdown()
            for n in nodes:
                n.shutdown()


class TestSignatures:
    @staticmethod
    def _mk(ident):
        return [SignatureValidator(ident)]

    @staticmethod
    def _by_clean_subkey(got):
        return {strip_owner(k): v for k, v in (got or {}).items()}

    def test_signed_roundtrip(self):
        nodes = make_swarm(3, validators=self._mk)
        try:
            exp = get_dht_time() + 60
            nodes[0].store("signed", "me", {"loss": 1.5}, exp)
            got = self._by_clean_subkey(nodes[2].get("signed"))
            assert got[b"me"].value == {"loss": 1.5}
        finally:
            for n in nodes:
                n.shutdown()

    def test_forged_record_rejected(self):
        """A peer storing under another's owner marker gets dropped on read
        (the reference's RSA validator guarantee, utils.py:27-30)."""
        honest, reader = Identity.generate(), Identity.generate()
        nodes = []
        nodes.append(DHT(identity=honest,
                         record_validators=[SignatureValidator(honest)],
                         rpc_timeout=2.0))
        forger_ident = Identity.generate()
        forger = DHT(initial_peers=[nodes[0].visible_address],
                     identity=forger_ident, rpc_timeout=2.0)  # no validator
        nodes.append(forger)
        nodes.append(DHT(initial_peers=[nodes[0].visible_address],
                         identity=reader,
                         record_validators=[SignatureValidator(reader)],
                         rpc_timeout=2.0))
        try:
            exp = get_dht_time() + 60
            # forge: subkey claims honest's identity, signature is garbage
            marker = SignatureValidator(honest).ownership_marker
            import msgpack as _mp
            forged_val = _mp.packb("forged") + b"\x00" * 64
            forger._lib.swarm_node_store(
                forger._node, __import__("hashlib").sha256(b"sig-k").digest(),
                b"victim" + marker, len(b"victim" + marker),
                forged_val, len(forged_val), exp)
            got = self._by_clean_subkey(nodes[2].get("sig-k"))
            assert b"victim" not in got
            # while a genuinely signed record passes
            nodes[0].store("sig-k", "victim", "real", exp)
            got = self._by_clean_subkey(nodes[2].get("sig-k"))
            assert got[b"victim"].value == "real"
        finally:
            for n in nodes:
                n.shutdown()

    def test_unsigned_cannot_shadow_signed(self):
        """An unsigned record with the bare subkey must not displace a
        signed one, and protected keys reject unsigned records entirely."""
        honest = Identity.generate()
        reader_v = [SignatureValidator(Identity.generate(),
                                       protected_keys=["guarded"])]
        bootstrap = DHT(identity=honest,
                        record_validators=[SignatureValidator(
                            honest, protected_keys=["guarded"])],
                        rpc_timeout=2.0)
        attacker = DHT(initial_peers=[bootstrap.visible_address],
                       rpc_timeout=2.0)  # writes unsigned records
        reader = DHT(initial_peers=[bootstrap.visible_address],
                     record_validators=reader_v, rpc_timeout=2.0)
        try:
            t = get_dht_time()
            bootstrap.store("guarded", "victim", "signed-truth", t + 30)
            attacker.store("guarded", "victim", "poison", t + 3000)
            got = reader.get("guarded")
            values = [v.value for v in got.values()]
            assert values == ["signed-truth"]
        finally:
            for n in (bootstrap, attacker, reader):
                n.shutdown()


class LocalMetrics(BaseModel):
    """Reference utils.py:15-21 schema."""
    step: conint(ge=0, strict=True)
    samples_per_second: StrictFloat
    samples_accumulated: StrictInt
    loss: StrictFloat
    mini_steps: StrictInt


class TestSchema:
    def test_schema_rejects_malformed(self):
        schemas = {"m_metrics": LocalMetrics}

        def mk(ident):
            return [SchemaValidator(schemas)]

        nodes = make_swarm(3, validators=mk)
        try:
            exp = get_dht_time() + 60
            good = {"step": 1, "samples_per_second": 8.0,
                    "samples_accumulated": 64, "loss": 2.5, "mini_steps": 4}
            nodes[0].store("m_metrics", "p0", good, exp)
            nodes[1].store("m_metrics", "p1", {"step": "NaN-garbage"}, exp)
            got = nodes[2].get("m_metrics")
            assert b"p0" in got and b"p1" not in got
            # non-schema'd keys unaffected
            nodes[0].store("other", "x", "anything", exp)
            assert nodes[2].get("other")[b"x"].value == "anything"
        finally:
            for n in nodes:
                n.shutdown()


class TestDataPlane:
    def test_send_recv_tagged_fifo(self, swarm5):
        addr = swarm5[3].visible_address
        assert swarm5[0].send(addr, tag=7, payload=b"part-0")
        assert swarm5[1].send(addr, tag=7, payload=b"part-1")
        assert swarm5[2].send(addr, tag=9, payload=b"other-channel")
        assert swarm5[3].recv(9, timeout=2.0) == b"other-channel"
        first = swarm5[3].recv(7, timeout=2.0)
        second = swarm5[3].recv(7, timeout=2.0)
        assert {first, second} == {b"part-0", b"part-1"}

    def test_recv_timeout_returns_none(self, swarm5):
        t0 = time.monotonic()
        assert swarm5[0].recv(12345, timeout=0.3) is None
        assert 0.2 < time.monotonic() - t0 < 2.0

    def test_large_payload(self, swarm5):
        blob = bytes(range(256)) * 4096 * 4  # 4 MiB tensor part
        assert swarm5[0].send(swarm5[1].visible_address, 1, blob)
        assert swarm5[1].recv(1, timeout=5.0) == blob

    def test_send_to_dead_peer_fails_fast(self, swarm5):
        t0 = time.monotonic()
        ok = swarm5[0].send("127.0.0.1:1", tag=1, payload=b"x")
        assert not ok
        assert time.monotonic() - t0 < 3.0

    def test_mailbox_post_fetch(self, swarm5):
        addr = swarm5[0].visible_address
        assert swarm5[0].post(42, b"averaged-part", get_dht_time() + 10)
        assert swarm5[1].fetch(addr, 42) == b"averaged-part"
        assert swarm5[1].fetch(addr, 43) is None
        # repost replaces
        assert swarm5[0].post(42, b"v2", get_dht_time() + 10)
        assert swarm5[2].fetch(addr, 42) == b"v2"

    def test_mailbox_expiry(self, swarm5):
        addr = swarm5[0].visible_address
        swarm5[0].post(7, b"ephemeral", get_dht_time() + 0.3)
        assert swarm5[1].fetch(addr, 7) == b"ephemeral"
        time.sleep(0.5)
        assert swarm5[1].fetch(addr, 7) is None

    def test_client_mode_can_fetch(self):
        nodes = make_swarm(2)
        client = DHT(initial_peers=[nodes[0].visible_address],
                     client_mode=True, rpc_timeout=2.0)
        try:
            nodes[1].post(9, b"for-the-client", get_dht_time() + 10)
            assert client.fetch(nodes[1].visible_address, 9) \
                == b"for-the-client"
        finally:
            client.shutdown()
            for n in nodes:
                n.shutdown()


class TestIdentity:
    def test_persisted_identity_roundtrip(self, tmp_path):
        p = str(tmp_path / "id.pem")
        a = Identity.load_or_create(p)
        b = Identity.load_or_create(p)
        assert a.node_id == b.node_id
        assert Identity.generate().node_id != a.node_id

    def test_sign_verify(self):
        ident = Identity.generate()
        sig = ident.sign(b"msg")
        assert Identity.verify(ident.public_bytes, sig, b"msg")
        assert not Identity.verify(ident.public_bytes, sig, b"tampered")


class TestRelay:
    """Relay mode: a routable peer forwards traffic between client-mode
    peers that cannot reach each other (VERDICT r2 next #3; the
    reference's libp2p relay surface, arguments.py:89-124)."""

    def test_relayed_send_and_fetch(self):
        relay = DHT(rpc_timeout=2.0)
        a = DHT(client_mode=True, rpc_timeout=2.0,
                initial_peers=[relay.visible_address])
        b = DHT(client_mode=True, rpc_timeout=2.0,
                initial_peers=[relay.visible_address])
        try:
            assert a.attach_relay(relay.visible_address)
            assert b.attach_relay(relay.visible_address)
            assert "/" in a.visible_address  # relay-routed form

            # push: a -> (relay) -> b lands in b's normal recv queue
            assert a.send(b.visible_address, 42, b"hello-b", timeout=3.0)
            assert b.recv(42, timeout=3.0) == b"hello-b"

            # mailbox through the relay: b posts locally, a fetches
            # through b's attachment
            assert b.post(7, b"parked", expiration_time=get_dht_time() + 30)
            got = a.fetch(b.visible_address, 7, timeout=3.0)
            assert got == b"parked"
            # absent tags miss cleanly
            assert a.fetch(b.visible_address, 999, timeout=2.0) is None
        finally:
            for n in (a, b, relay):
                n.shutdown()

    def test_detached_target_misses(self):
        relay = DHT(rpc_timeout=2.0)
        a = DHT(client_mode=True, rpc_timeout=2.0)
        b = DHT(client_mode=True, rpc_timeout=2.0)
        try:
            assert a.attach_relay(relay.visible_address)
            fake = f"{relay.visible_address}/{b.peer_id}"
            assert not a.send(fake, 1, b"x", timeout=2.0)
            assert a.fetch(fake, 1, timeout=2.0) is None
        finally:
            for n in (a, b, relay):
                n.shutdown()


class TestHolePunch:
    """DHT-coordinated TCP hole punch (VERDICT r3 next #7): two
    listener-less peers establish a direct link coordinated through the
    DHT; relayed sends/fetches then bypass the relay, and fall back to
    it when the punch never happened or the link dies."""

    def _mesh(self):
        relay = DHT(rpc_timeout=2.0)
        a = DHT(client_mode=True, rpc_timeout=2.0,
                initial_peers=[relay.visible_address])
        b = DHT(client_mode=True, rpc_timeout=2.0,
                initial_peers=[relay.visible_address])
        assert a.attach_relay(relay.visible_address)
        assert b.attach_relay(relay.visible_address)
        return relay, a, b

    def test_punch_then_direct_traffic_bypasses_relay(self):
        import threading

        relay, a, b = self._mesh()
        try:
            results = {}

            def punch(me, other, key):
                results[key] = me.punch(other.visible_address, timeout=10.0)

            ta = threading.Thread(target=punch,
                                  args=(a, b, "a"))
            tb = threading.Thread(target=punch, args=(b, a, "b"))
            ta.start(), tb.start()
            ta.join(20), tb.join(20)
            assert results.get("a") and results.get("b"), results
            assert a.has_direct(b.visible_address)
            assert b.has_direct(a.visible_address)

            base = relay.relay_traffic_served
            # pushes ride the punched link...
            assert a.send(b.visible_address, 77, b"direct!", timeout=3.0)
            assert b.recv(77, timeout=3.0) == b"direct!"
            # ...and so do mailbox fetches
            assert b.post(78, b"parked", expiration_time=get_dht_time() + 30)
            assert a.fetch(b.visible_address, 78, timeout=3.0) == b"parked"
            assert a.fetch(b.visible_address, 999, timeout=2.0) is None
            assert relay.relay_traffic_served == base, \
                "direct traffic still transited the relay"
        finally:
            for n in (a, b, relay):
                n.shutdown()

    def test_without_punch_relay_carries_traffic(self):
        relay, a, b = self._mesh()
        try:
            base = relay.relay_traffic_served
            assert a.send(b.visible_address, 80, b"via-relay", timeout=3.0)
            assert b.recv(80, timeout=3.0) == b"via-relay"
            assert relay.relay_traffic_served > base
        finally:
            for n in (a, b, relay):
                n.shutdown()

    def test_one_sided_punch_times_out_and_relay_still_works(self):
        relay, a, b = self._mesh()
        try:
            # only one side punches: no rendezvous, clean failure
            assert not a.punch(b.visible_address, timeout=2.0)
            assert not a.has_direct(b.visible_address)
            assert a.send(b.visible_address, 81, b"fallback", timeout=3.0)
            assert b.recv(81, timeout=3.0) == b"fallback"
        finally:
            for n in (a, b, relay):
                n.shutdown()


class TestRelayedAddressParsing:
    def test_attach_relay_accepts_relayed_address(self):
        """The banner advertises ``host:port/<peer id>`` as the copyable
        --initial-peers entry; attach_relay must accept that form and
        attach to the relay's host:port (ADVICE r3: rpartition(':')
        raised ValueError on the suffix)."""
        relay = DHT(rpc_timeout=2.0)
        a = DHT(client_mode=True, rpc_timeout=2.0)
        b = DHT(client_mode=True, rpc_timeout=2.0,
                initial_peers=[relay.visible_address])
        try:
            relayed_form = f"{relay.visible_address}/{relay.peer_id}"
            assert a.attach_relay(relayed_form)
            assert b.attach_relay(relay.visible_address)
            # the attachment is functional, not just rc==0
            assert b.send(a.visible_address, 11, b"via-relay", timeout=3.0)
            assert a.recv(11, timeout=3.0) == b"via-relay"
        finally:
            for n in (a, b, relay):
                n.shutdown()


def _frame_server(replies):
    """Loopback fake endpoint speaking the daemon's u32-length framing.

    ``replies`` maps the i-th received frame (across all connections) to
    a reply payload, ``("reply_close", payload)`` (reply, then close the
    connection cleanly — FIN reaches the client's pooled socket), or
    ``None`` (swallow the request: the client's read times out).
    Returns (port, frames, conns, closer).
    """
    import socket
    import threading

    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    frames, conns = [], []

    def recv_exact(c, n):
        buf = b""
        while len(buf) < n:
            chunk = c.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf

    def handle(c):
        while True:
            hdr = recv_exact(c, 4)
            if hdr is None:
                return
            ln = int.from_bytes(hdr, "big")
            payload = recv_exact(c, ln)
            if payload is None:
                return
            idx = len(frames)
            frames.append(payload)
            action = replies.get(idx, None)
            if isinstance(action, tuple) and action[0] == "reply_close":
                c.sendall(len(action[1]).to_bytes(4, "big") + action[1])
                c.close()  # handler exits: FIN lands while client idles
                return
            if action is not None:
                c.sendall(len(action).to_bytes(4, "big") + action)

    def accept_loop():
        while True:
            try:
                c, _ = srv.accept()
            except OSError:
                return
            conns.append(c)
            threading.Thread(target=handle, args=(c,),
                             daemon=True).start()

    threading.Thread(target=accept_loop, daemon=True).start()
    return srv.getsockname()[1], frames, conns, srv.close


MSG_OK = bytes([10])  # kMsgOk


class TestPooledRpcSafety:
    """ADVICE r3 (swarm.cc rpc retry): a resend is only safe while the
    server cannot have acted on the request. These tests drive the
    client's rpc() against a scripted fake endpoint."""

    def test_lost_reply_is_hard_failure_no_duplicate(self):
        """Reply lost AFTER the server consumed the request: the client
        must fail the call without resending — kMsg is not idempotent
        and the all-reduce part exchange does not de-duplicate."""
        port, frames, conns, closer = _frame_server(
            {0: MSG_OK, 1: None})  # swallow the 2nd request's reply
        node = DHT(rpc_timeout=2.0)
        try:
            addr = f"127.0.0.1:{port}"
            assert node.send(addr, 1, b"first", timeout=2.0)   # pools fd
            assert not node.send(addr, 2, b"second", timeout=1.0)
            time.sleep(1.5)  # a would-be retry fires within the timeout
            assert len(frames) == 2, (
                f"server saw {len(frames)} frames: lost-reply retry "
                f"delivered a duplicate")
        finally:
            closer()
            node.shutdown()

    def test_stale_pooled_socket_reconnects(self):
        """Server closed the pooled connection while idle: the pre-write
        probe must detect the dead socket and the call must complete on
        a fresh connection (exactly one delivery of each request)."""
        port, frames, conns, closer = _frame_server(
            {0: ("reply_close", MSG_OK), 1: MSG_OK})
        node = DHT(rpc_timeout=2.0)
        try:
            addr = f"127.0.0.1:{port}"
            assert node.send(addr, 1, b"first", timeout=2.0)
            time.sleep(0.3)    # let the server's FIN land
            assert node.send(addr, 2, b"second", timeout=2.0)
            assert len(frames) == 2
            assert len(conns) == 2  # second send went over a fresh fd
        finally:
            closer()
            node.shutdown()


class TestConnectionReuse:
    def test_many_rpcs_per_connection_latency(self):
        """The data plane keeps one pooled connection per endpoint (a TCP
        connect per RPC pays an extra round trip on real links). Checked
        functionally (hundreds of sequential RPCs work, surviving the
        pool) plus a loopback latency bound that per-RPC connects made
        flaky-slow."""
        a, b = make_swarm(2)
        try:
            payload = b"x" * 1024
            # warm the pool + queues
            for i in range(5):
                assert a.send(b.visible_address, 5, payload, timeout=2.0)
            t0 = time.monotonic()
            n = 300
            for i in range(n):
                assert a.send(b.visible_address, 5, payload, timeout=2.0)
            dt = time.monotonic() - t0
            for _ in range(n + 5):
                assert b.recv(5, timeout=2.0) is not None
            # loopback pooled RPC ~100us; allow a loaded-box margin
            assert dt / n < 0.005, f"{1e6 * dt / n:.0f}us per pooled RPC"
        finally:
            a.shutdown()
            b.shutdown()


def test_eight_peer_scale_run():
    """VERDICT r2 next #4: 8 real peers on loopback (full + client +
    relay-attached mix), a mid-run kill and a mid-run join, all through
    the real wire stack. The script asserts >= N-1 peers finish all
    epochs and prints the SWARM_SCALE.md timing table."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    # loaded-CI headroom: fewer epochs, longer deadline than the
    # interactive bench defaults
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               SWARM_SCALE_EPOCHS="3", SWARM_SCALE_DEADLINE="300")
    res = subprocess.run(
        [sys.executable, str(repo / "scripts" / "swarm_scale_bench.py"),
         "8"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=420)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-2000:]
    assert "peers reached epoch" in res.stdout


class TestRendezvous:
    """Rendezvous bootstrap (swarm/rendezvous.py): the IPFS-assisted
    bootstrap analogue (reference arguments.py:100-106) — shared-file
    first contact + DHT-key list repair."""

    def test_file_publish_and_fresh_peers(self, tmp_path):
        from dalle_tpu.swarm.rendezvous import RendezvousFile

        f = RendezvousFile(str(tmp_path / "rdv.txt"), max_age=60.0)
        assert f.fresh_peers() == []
        f.publish("peerA", "127.0.0.1:1111")
        f.publish("peerB", "127.0.0.1:2222")
        assert f.fresh_peers() == ["127.0.0.1:1111", "127.0.0.1:2222"]
        # re-publish replaces the peer's previous line
        f.publish("peerA", "127.0.0.1:3333")
        assert "127.0.0.1:1111" not in f.fresh_peers()
        # self-exclusion and pull-only (empty addr) no-op
        assert f.fresh_peers(exclude_peer_id="peerB") == ["127.0.0.1:3333"]
        f.publish("peerC", "")
        assert len(f.fresh_peers()) == 2

    def test_flock_failure_warns_once(self, tmp_path, caplog, monkeypatch):
        """A lockless filesystem (flock -> OSError) must be loud ONCE:
        the unlocked fallback can lose concurrent publishers' lines
        (ADVICE r5), and silent data-plane surprises are how rendezvous
        debugging sessions start."""
        import fcntl
        import logging

        from dalle_tpu.swarm import rendezvous

        def broken_flock(*a, **k):
            raise OSError("no lockd on this mount")

        monkeypatch.setattr(fcntl, "flock", broken_flock)
        monkeypatch.setattr(rendezvous, "_FLOCK_WARNED", False)
        f = rendezvous.RendezvousFile(str(tmp_path / "rdv.txt"))
        with caplog.at_level(logging.WARNING,
                             logger="dalle_tpu.swarm.rendezvous"):
            f.publish("peerA", "127.0.0.1:1111")
            f.publish("peerB", "127.0.0.1:2222")
        warns = [r for r in caplog.records
                 if "lock unavailable" in r.message]
        assert len(warns) == 1  # once, not per publish
        # the publishes themselves still landed
        assert len(f.fresh_peers()) == 2

    def test_stale_entries_age_out(self, tmp_path):
        from dalle_tpu.swarm.rendezvous import RendezvousFile

        f = RendezvousFile(str(tmp_path / "rdv.txt"), max_age=0.2)
        f.publish("peerA", "127.0.0.1:1111")
        assert f.fresh_peers() == ["127.0.0.1:1111"]
        time.sleep(0.3)
        assert f.fresh_peers() == []
        # a new publish compacts the stale line away
        f.publish("peerB", "127.0.0.1:2222")
        with open(f.path) as fh:
            assert "peerA" not in fh.read()

    def test_dht_advertise_and_discover(self, swarm5):
        from dalle_tpu.swarm.rendezvous import advertise, discover

        for node in swarm5:
            advertise(node, "exp")
        time.sleep(0.2)
        found = discover(swarm5[0], "exp")
        others = {n.visible_address for n in swarm5[1:]}
        assert others.issubset(set(found))
        assert swarm5[0].visible_address not in found  # self excluded

    def test_file_bootstrap_forms_swarm(self, tmp_path):
        """A joiner with NO initial peers finds the swarm through the
        rendezvous file alone (the zero-config first contact)."""
        from dalle_tpu.swarm.rendezvous import RendezvousFile

        f = RendezvousFile(str(tmp_path / "rdv.txt"))
        seed = DHT(initial_peers=[], identity=Identity.generate(),
                   rpc_timeout=2.0)
        try:
            f.publish(seed.peer_id, seed.visible_address)
            joiner = DHT(initial_peers=f.fresh_peers(),
                         identity=Identity.generate(), rpc_timeout=2.0)
            try:
                exp = get_dht_time() + 30
                assert joiner.store("k", "sub", {"v": 1}, exp)
                deadline = time.monotonic() + 5
                got = None
                while time.monotonic() < deadline and not got:
                    got = seed.get("k")
                    time.sleep(0.1)
                assert got and "v" in next(iter(got.values())).value
            finally:
                joiner.shutdown()
        finally:
            seed.shutdown()

    def test_concurrent_publishers_all_land(self, tmp_path):
        """N simultaneous publishers must not clobber each other's lines
        (the locked read-modify-write, r5 review finding)."""
        import threading

        from dalle_tpu.swarm.rendezvous import RendezvousFile

        f = RendezvousFile(str(tmp_path / "rdv.txt"))
        n = 8
        threads = [threading.Thread(
            target=lambda i=i: f.publish(f"peer{i}", f"127.0.0.1:{1000+i}"))
            for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(f.fresh_peers()) == n
