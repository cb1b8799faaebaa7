"""tcp_window: the cross-host one-sided ring domain over sockets.

The second real implementation of the MemoryDomain seam (VERDICT r2 next#5):
the identical pair/ring/credit protocol that runs over /dev/shm runs across
process (and host) boundaries over an ordered record socket — the role the
reference's RDMA WRITE fabric plays (``pair.cc:587-622``). No shared memory
exists between the peers in any test here.
"""

import os
import socket
import subprocess
import sys
import threading
import time

import pytest

import tpurpc.core.pair as P
from tpurpc.core.pair import Pair, PairState, create_loopback_pair
from tpurpc.core.poller import wait_readable
from tpurpc.core.tcpw import TcpWindowDomain, _PeerLink, _RecordServer


def test_tcpw_same_process_roundtrip():
    a, b = create_loopback_pair(ring_size=4096, domain=TcpWindowDomain())
    try:
        a.send([b"over the record socket"])
        assert wait_readable(b, timeout=10, discipline="event")
        assert b.recv() == b"over the record socket"
        # and the reverse direction
        b.send([b"back"])
        assert wait_readable(a, timeout=10, discipline="event")
        assert a.recv() == b"back"
    finally:
        a.destroy()
        b.destroy()


def test_tcpw_large_messages_wrap_and_credits():
    """Messages larger than the ring force wrap-split writes, partial sends,
    and credit returns — all riding the record stream's ordering."""
    a, b = create_loopback_pair(ring_size=4096, domain=TcpWindowDomain())
    try:
        payload = bytes(range(256)) * 64  # 16 KiB through a 4 KiB ring
        done = threading.Event()

        def pump():
            # partial sends are the contract (rdma_flush loop analog):
            # resume as credits arrive over the record stream
            sent = 0
            while sent < len(payload):
                n = a.send([payload], sent)
                sent += n
                if n == 0:
                    time.sleep(0.002)  # credits in flight
            done.set()

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        got = b""
        deadline = time.monotonic() + 20
        while len(got) < len(payload) and time.monotonic() < deadline:
            if wait_readable(b, timeout=5, discipline="event"):
                got += b.recv()
        assert got == payload
        assert done.wait(5)
    finally:
        a.destroy()
        b.destroy()


def test_tcpw_stale_write_discarded():
    """A write racing region teardown is dropped (deregistered-MR analog),
    never applied to freed memory and never a crash."""
    dom = TcpWindowDomain()
    region = dom.alloc(1024)
    win = dom.open_window(region.handle, 1024)
    win.write(0, b"live")
    deadline = time.monotonic() + 5
    while bytes(region.buf[:4]) != b"live" and time.monotonic() < deadline:
        time.sleep(0.01)
    assert bytes(region.buf[:4]) == b"live"
    region.close()  # unregisters the key
    win.write(0, b"dead")  # must be discarded server-side
    time.sleep(0.2)
    win.close()


def test_tcpw_out_of_bounds_write_discarded():
    dom = TcpWindowDomain()
    region = dom.alloc(64)
    win = dom.open_window(region.handle, 64)
    win.write(60, b"0123456789")  # runs past the region: dropped whole
    win.write(0, b"ok")
    deadline = time.monotonic() + 5
    while bytes(region.buf[:2]) != b"ok" and time.monotonic() < deadline:
        time.sleep(0.01)
    assert bytes(region.buf[:2]) == b"ok"
    assert bytes(region.buf[60:]) == b"\0\0\0\0"
    win.close()
    region.close()


def test_tcpw_windows_share_one_ordered_link():
    """All windows to one peer process share a single connection — the RC-QP
    total-order property the ring protocol's publication invariant needs
    (data write then credit write must never be observed reordered)."""
    dom = TcpWindowDomain()
    r1, r2 = dom.alloc(128), dom.alloc(128)
    w1 = dom.open_window(r1.handle, 128)
    w2 = dom.open_window(r2.handle, 128)
    host_port = r1.handle.rsplit(":", 2)[0][5:], None
    with _PeerLink._links_lock:
        assert len([k for k in _PeerLink._links]) >= 1
        # both windows resolved to the same (host, port) → same link
        server = _RecordServer.get()
        link_keys = {k for k in _PeerLink._links if k[1] == server.port}
        assert len(link_keys) == 1
    for i in range(50):  # interleave; ordering is per-link FIFO
        w1.write(0, bytes([i]))
        w2.write(0, bytes([i]))
    deadline = time.monotonic() + 5
    while (region_bytes := (r1.buf[0], r2.buf[0])) != (49, 49) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert region_bytes == (49, 49)
    for x in (w1, w2, r1, r2):
        x.close()


def test_tcpw_cross_process_echo():
    """Two processes, no shared memory: rings live in each process's private
    heap; every one-sided write crosses a real socket."""
    parent_sock, child_sock = socket.socketpair()
    pid = os.fork()
    if pid == 0:
        status = 1
        try:
            parent_sock.close()
            pair = Pair(TcpWindowDomain(), ring_size=8192)
            pair.init()
            pair.connect_over_socket(child_sock)
            echoed = 0
            while echoed < 3:
                if wait_readable(pair, timeout=10, discipline="event"):
                    data = pair.recv()
                    if data:
                        pair.send([b"echo:", data])
                        echoed += 1
                    elif pair.get_status() is not PairState.CONNECTED:
                        break
            pair.destroy()
            status = 0
        finally:
            os._exit(status)
    child_sock.close()
    pair = Pair(TcpWindowDomain(), ring_size=8192)
    pair.init()
    pair.connect_over_socket(parent_sock)
    try:
        for i in range(3):
            msg = f"msg-{i}".encode() * (i + 1)
            pair.send([msg])
            got = b""
            deadline = time.monotonic() + 10
            while len(got) < len(msg) + 5 and time.monotonic() < deadline:
                if wait_readable(pair, timeout=5, discipline="event"):
                    got += pair.recv()
            assert got == b"echo:" + msg
        pair.disconnect()
    finally:
        pair.destroy()
        _, code = os.waitpid(pid, 0)
        assert os.waitstatus_to_exitcode(code) == 0


def test_tcpw_domain_mismatch_rejected():
    """A tcp_window peer meeting an shm peer fails loudly at bootstrap
    (the reference asserts tag/ring-size match the same way)."""
    a = Pair(TcpWindowDomain(), ring_size=4096)
    b = Pair(P.ShmDomain(), ring_size=4096)
    a.init()
    b.init()
    sa, sb = socket.socketpair()
    errs = []

    def side(pair, sock):
        try:
            pair.connect_over_socket(sock)
        except ValueError as exc:
            errs.append(str(exc))

    t = threading.Thread(target=side, args=(b, sb), daemon=True)
    t.start()
    side(a, sa)
    t.join(10)
    a.destroy()
    b.destroy()
    assert any("domain mismatch" in e for e in errs)


def _run_cross_process(server_src: str, client_src: str, env: dict,
                       client_timeout: float = 120) -> None:
    """Spawn the server script, read its port with a bounded wait, run the
    client script against it, kill the server. One copy of the hazards:
    readline can't hang the suite (selector-bounded), a bad first line
    kills the child BEFORE draining stderr (so the read sees EOF), and the
    child is killed in finally."""
    import selectors

    srv = subprocess.Popen([sys.executable, "-c", server_src],
                           stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                           text=True, env=env)
    try:
        sel = selectors.DefaultSelector()
        sel.register(srv.stdout, selectors.EVENT_READ)
        if not sel.select(timeout=120):
            srv.kill()
            raise AssertionError("server never printed its port: "
                                 + srv.stderr.read()[:2000])
        port = srv.stdout.readline().strip()
        if not port.isdigit():
            srv.kill()
            raise AssertionError(f"bad port line {port!r}: "
                                 + srv.stderr.read()[:2000])
        cli = subprocess.run([sys.executable, "-c", client_src, port],
                             capture_output=True, text=True, env=env,
                             timeout=client_timeout)
        assert cli.returncode == 0, cli.stderr
        assert "CLIENT_OK" in cli.stdout
    finally:
        srv.kill()
        srv.wait()


_RPC_SERVER = r"""
import sys
import tpurpc.rpc as rpc

srv = rpc.Server(max_workers=4)
srv.add_method("/x.S/Echo", rpc.unary_unary_rpc_method_handler(
    lambda req, ctx: bytes(req) + b"/tcpw"))
port = srv.add_insecure_port("127.0.0.1:0")
srv.start()
print(port, flush=True)
srv.wait_for_termination(timeout=120)
"""

_RPC_CLIENT = r"""
import sys
import tpurpc.rpc as rpc
from tpurpc.utils.config import get_config

assert get_config().ring_domain == "tcp_window", get_config().ring_domain
with rpc.insecure_channel(f"127.0.0.1:{sys.argv[1]}") as ch:
    echo = ch.unary_unary("/x.S/Echo")
    for i in range(5):
        assert echo(b"m%d" % i, timeout=30) == b"m%d/tcpw" % i
    # big payload: exercises chunking + credits across the record stream
    big = bytes(range(256)) * 4096  # 1 MiB
    assert echo(big, timeout=60) == big + b"/tcpw"
print("CLIENT_OK", flush=True)
"""


def test_tcpw_full_rpc_cross_process():
    """The capability the reference ships: unmodified RPC apps, fast pipe
    between (here: processes standing in for) hosts — selected purely by env
    (GRPC_PLATFORM_TYPE=RDMA_BP + TPURPC_RING_DOMAIN=tcp_window)."""
    env = dict(os.environ,
               GRPC_PLATFORM_TYPE="RDMA_BP",
               TPURPC_RING_DOMAIN="tcp_window",
               GRPC_RDMA_RING_BUFFER_SIZE_KB="256")
    _run_cross_process(_RPC_SERVER, _RPC_CLIENT, env)


def test_tcpw_qps_scenario():
    """The qps driver/worker rig (test/cpp/qps clone) runs its measured
    traffic over the tcp_window ring platform — the reference's distributed
    perf rig shape on the cross-host fabric (VERDICT r2 #5 'done' bar)."""
    code = (
        "import json\n"
        "from tpurpc.bench import qps\n"
        "from tpurpc.utils.config import get_config\n"
        "assert get_config().ring_domain == 'tcp_window'\n"
        "agg = qps.run_localhost(n_clients=2, req_size=64, duration=1.5,"
        " concurrency=1)\n"
        "print(json.dumps({'rpcs': agg['rpcs'], 'rate': agg['rate_rps']}))\n"
    )
    env = dict(os.environ,
               GRPC_PLATFORM_TYPE="RDMA_BP",
               TPURPC_RING_DOMAIN="tcp_window",
               GRPC_RDMA_RING_BUFFER_SIZE_KB="256")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=180)
    assert out.returncode == 0, out.stderr
    stats = __import__("json").loads(out.stdout.strip().splitlines()[-1])
    assert stats["rpcs"] > 20 and stats["rate"] > 0


_TPU_TCPW_SERVER = r"""
import os, sys
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import tpurpc.rpc as rpc
from tpurpc.jaxshim import add_tensor_method
from tpurpc.utils.config import get_config

assert get_config().ring_domain == "tcp_window", get_config().ring_domain
seen = {}

def fn(tree):
    import jax
    seen["ok"] = isinstance(tree["x"], jax.Array)
    return {"y": np.asarray(tree["x"]) * 3, "ring": np.int64(seen["ok"])}

srv = rpc.Server(max_workers=4)
add_tensor_method(srv, "Call", fn, device=True)
port = srv.add_insecure_port("127.0.0.1:0")
srv.start()
print(port, flush=True)
srv.wait_for_termination(timeout=120)  # orphan self-reaps if pytest dies
"""

_TPU_TCPW_CLIENT = r"""
import os, sys
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from tpurpc.jaxshim import TensorClient
from tpurpc.rpc.channel import Channel
from tpurpc.utils.config import get_config

assert get_config().ring_domain == "tcp_window"
x = np.arange(2048, dtype=np.float32).reshape(64, 32)
with Channel(f"127.0.0.1:{sys.argv[1]}") as ch:
    out = TensorClient(ch).call("Call", {"x": x}, timeout=60)
np.testing.assert_array_equal(np.asarray(out["y"]), x * 3)
assert int(np.asarray(out["ring"]).ravel()[0]) == 1  # device-ring-backed
print("CLIENT_OK", flush=True)
"""


def test_tpu_platform_over_tcpw_cross_process():
    """The north-star topology composed: GRPC_PLATFORM_TYPE=TPU (payloads
    land in the receiver's DEVICE ring, handler gets lease-backed
    jax.Arrays) x TPURPC_RING_DOMAIN=tcp_window (the one-sided ring carried
    between PROCESSES standing in for hosts). Tensor bytes from another
    process land in the device ring purely by env selection."""
    env = dict(os.environ,
               GRPC_PLATFORM_TYPE="TPU",
               TPURPC_RING_DOMAIN="tcp_window",
               GRPC_RDMA_RING_BUFFER_SIZE_KB="1024",
               JAX_PLATFORMS="cpu")
    _run_cross_process(_TPU_TCPW_SERVER, _TPU_TCPW_CLIENT, env,
                       client_timeout=240)


def test_forged_records_cannot_land_bytes():
    """VERDICT r3 #8: write authorization is possession of the per-region
    HMAC secret (delivered only via the handle, i.e. the bootstrap channel)
    — an attacker who knows everything ON THE WIRE short of the secret
    (host, port, hello, region key, record format) cannot land a byte."""

    from tpurpc.core import tcpw as T

    dom = TcpWindowDomain()
    region = dom.alloc(256)
    # the 16B region key is the wire-visible identifier; the secret is not
    _, _, key_hex, _secret_hex = region.handle[5:].rsplit(":", 3)
    key = bytes.fromhex(key_hex)
    server = _RecordServer.get()

    def forge(records, hello=T._HELLO):
        s = socket.create_connection(("127.0.0.1", server.port), timeout=5)
        try:
            try:
                s.sendall(hello)
                for rec in records:
                    s.sendall(rec)
            except (BrokenPipeError, ConnectionResetError):
                return b""  # server dropped us mid-send: same verdict
            # server closes on verification failure; a clean read of 0
            # bytes = dropped connection (it never writes back otherwise)
            s.settimeout(5)
            try:
                return s.recv(1)
            except socket.timeout:
                return b"open"
            except ConnectionResetError:
                return b""  # dropped with unread bytes pending: RST
        finally:
            s.close()

    payload = b"A" * 32
    hdr = T._REC.pack(key, 0, len(payload))

    # (1) garbage MAC: dropped, nothing lands
    assert forge([hdr + b"\x00" * T._MAC_LEN + payload]) == b""
    # (2) MAC computed with the WRONG secret: dropped, nothing lands
    bad = T._record_mac(b"x" * 32, hdr, payload)
    assert forge([hdr + bad + payload]) == b""
    # (3) pure garbage stream: dropped at the hello
    assert forge([b"\xde\xad" * 40], hello=b"XXXX") == b""
    # (4) oversized length field (payload > region): skimmed through a
    # bounded scratch — no region-sized allocation, nothing lands, and a
    # single offense keeps the connection (legit teardown races look the
    # same) rather than dropping it
    big_hdr = T._REC.pack(key, 0, 1024)
    assert forge([big_hdr + b"\x00" * T._MAC_LEN + b"B" * 1024]) == b"open"
    # (5) unknown-key flood: the per-connection unverifiable budget runs
    # out (it only replenishes on VERIFIED records, which a forger can't
    # produce) and the connection is dropped — no infinite free probing
    flood = []
    for i in range(1100):
        fh = T._REC.pack(os.urandom(16), 0, 4)
        flood.append(fh + b"\x00" * T._MAC_LEN + b"XXXX")
    assert forge(flood) == b""
    time.sleep(0.1)
    assert bytes(region.buf) == b"\0" * 256, "forged bytes landed!"

    # (4) the LEGITIMATE path (handle carries the secret) still works
    win = dom.open_window(region.handle, 256)
    win.write(0, b"legit")
    deadline = time.monotonic() + 5
    while bytes(region.buf[:5]) != b"legit" and time.monotonic() < deadline:
        time.sleep(0.01)
    assert bytes(region.buf[:5]) == b"legit"
    win.close()
    region.close()
