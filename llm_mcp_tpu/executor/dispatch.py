"""Dispatch plane: ONE scheduling loop, two backends.

`GenerationEngine` (executor/engine.py) owns ALL policy — admission,
token budgets, speculation, preemption, paging, the prefix tier. Every
mutation of device state funnels through a single choke point
(`GenerationEngine._dx(op, *args)`), and a `DispatchBackend` decides what
a dispatch *means*:

  - **LocalArraysBackend** — today's single-process path. `emit` is a
    no-op; `_dx` just executes the op closure against local arrays.
    Zero overhead, byte-identical behavior to the pre-dispatch engine.
  - **GSPMDBackend** — the multi-host path. The leader broadcasts each
    dispatch as a `("step", op, args)` frame over the command channel
    BEFORE executing it locally; followers replay the identical op
    closure against the same born-sharded global arrays. Multi-controller
    JAX treats the identical numpy payloads as replicated inputs, so the
    jitted programs — and therefore the tokens — cannot diverge.

The step-program is the WHOLE protocol. A follower's loop is four lines:
ping → continue, stop → return, step → `exec_table[op](*args)`. There is
no per-feature command handling anywhere — not here, not in the engine —
and the llmtpu-lint dispatch-surface pass keeps it that way: every op the
engine registers/dispatches must appear in `DISPATCH_OPS` below, and the
channel classes may not be touched outside this module.

Payload discipline (what makes replay sound): op args carry only host
values — numpy arrays, ints, floats, strings, bytes. Device state (the
weights, the KV cache, the physical pool, sampling rows) lives on `self`
inside the op closures, identical on every process by born-sharded
construction. Anything the leader must READ back (sampled tokens,
snapshot rows, prefix exports) comes out of a jit with a REPLICATED
out-sharding, so `np.asarray` on it is a local copy on every process.
"""

from __future__ import annotations

import os
import pickle
import socket
import struct
import threading
import time
from typing import Any, Callable, Mapping

__all__ = [
    "DISPATCH_OPS",
    "PING_INTERVAL_S",
    "CmdLeader",
    "CmdFollower",
    "DispatchBackend",
    "LocalArraysBackend",
    "GSPMDBackend",
]


# ---------------------------------------------------------------------------
# The op vocabulary: the COMPLETE device-mutation surface of the engine.
# llmtpu-lint (analysis/dispatch_surface.py) reconciles this tuple against
# the `ops[...] = ...` registry and every `_dx("...")` call site in
# engine.py, both ways — an op added on one side without the other fails CI.
# ---------------------------------------------------------------------------

DISPATCH_OPS = (
    "admit",    # fused admit: prefill + inserts + sampling rows + token0
    "insert",   # bulk row insert from a device prefix entry (hit, restore)
    "insrows",  # bulk row insert from host KV rows (restore, migrate-in)
    "insat",    # exact-length host-row insert at an offset (paged restore)
    "chunk",    # bucketed chunked-prefill group (logits park by gid)
    "ragged",   # ragged chunked-prefill group (logits park by gid)
    "bsample",  # boundary sample off a parked group's logits + row writes
    "decode",   # decode round (plain / fused-chunk / fused-ragged)
    "verify",   # speculative verify round
    "cnstep",   # grammar-constrained single-step decode (masked sample)
    "samprow",  # set one slot's sampling row (temp/top-k/top-p/last)
    "snap",     # replicate+fetch KV rows (preempt snapshot, migration)
    "pfxput",   # slice live rows into the device prefix cache
    "pfxdrop",  # release a device prefix entry
    "pfximp",   # materialize host bytes as a device prefix entry
    "pfxexp",   # replicate+fetch a prefix entry (fleet export)
    "poolexp",  # physical pool: replicate+fetch pool rows (fleet export)
    "cow",      # physical pool: copy-on-write one block
    "pput",     # physical pool: publish one block (arena/pool/host)
)


# ---------------------------------------------------------------------------
# Command channel: leader → followers, length-prefixed pickles over TCP
# ---------------------------------------------------------------------------


PING_INTERVAL_S = 5.0  # leader liveness beacon cadence while the queue is idle


class CmdLeader:
    """Leader side: accept one connection per follower, broadcast commands."""

    def __init__(self, bind_addr: str, n_followers: int, timeout_s: float = 60.0):
        host, _, port = bind_addr.rpartition(":")
        self._srv = socket.create_server((host or "0.0.0.0", int(port)))
        self._srv.settimeout(timeout_s)
        self.conns: list[socket.socket] = []
        # send() is called from the engine loop AND shutdown()'s thread (the
        # "stop" frame); interleaved sendall() would corrupt the frame stream
        self._send_lock = threading.Lock()
        self.last_send_t = time.monotonic()
        for _ in range(n_followers):
            c, _addr = self._srv.accept()
            c.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self.conns.append(c)

    def send(self, obj: Any) -> None:
        blob = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
        frame = struct.pack("<I", len(blob)) + blob
        with self._send_lock:
            for c in self.conns:
                c.sendall(frame)
            self.last_send_t = time.monotonic()

    def ping_if_idle(self, interval_s: float = PING_INTERVAL_S) -> None:
        """Beacon so followers can tell a quiet leader from a dead one."""
        if time.monotonic() - self.last_send_t >= interval_s:
            self.send(("ping",))

    def close(self) -> None:
        for c in self.conns:
            try:
                c.close()
            except OSError:
                pass
        self._srv.close()


class CmdFollower:
    """Follower side: connect (with retry — the leader may boot later) and
    wait on recv with a liveness bound: the leader beacons ("ping") every
    PING_INTERVAL_S while idle, so a follower that sees NO bytes for
    `idle_timeout_s` concludes the leader process is dead (not merely quiet)
    and raises instead of blocking forever on a half-open socket."""

    def __init__(self, addr: str, timeout_s: float = 60.0, idle_timeout_s: float = 600.0):
        host, _, port = addr.rpartition(":")
        deadline = time.time() + timeout_s
        while True:
            try:
                self._c = socket.create_connection((host, int(port)), timeout=5.0)
                break
            except OSError:
                if time.time() > deadline:
                    raise
                time.sleep(0.2)
        self._c.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # finite so recv wakes periodically to check the liveness deadline.
        # idle_timeout_s is deliberately generous: the leader stops beaconing
        # while ITS dispatch blocks (first-admit XLA compiles can run
        # minutes), so this guards against a dead leader, not a slow one.
        self.idle_timeout_s = max(idle_timeout_s, 1.0)
        self._c.settimeout(min(PING_INTERVAL_S, self.idle_timeout_s))

    def recv(self) -> Any:
        hdr = self._recv_exact(4)
        (n,) = struct.unpack("<I", hdr)
        return pickle.loads(self._recv_exact(n))

    def _recv_exact(self, n: int) -> bytes:
        buf = b""
        deadline = time.monotonic() + self.idle_timeout_s
        while len(buf) < n:
            try:
                chunk = self._c.recv(n - len(buf))
            except TimeoutError:
                if time.monotonic() > deadline:
                    raise ConnectionError(
                        f"leader sent nothing for {self.idle_timeout_s:.0f}s "
                        "(no command or ping): presumed dead"
                    ) from None
                continue
            if not chunk:
                raise ConnectionError("command channel closed")
            buf += chunk
            deadline = time.monotonic() + self.idle_timeout_s
        return buf

    def close(self) -> None:
        self._c.close()


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------


class DispatchBackend:
    """What a dispatch means. The engine is backend-agnostic: it calls
    `emit(op, args)` before running each op closure, `idle()` from quiet
    loop iterations, `stop()`/`close()` at shutdown, and hands its op
    registry to `run_follower(exec_table)` on non-leader processes."""

    #: True when device arrays are GLOBAL (multi-controller GSPMD): init
    #: must be born-sharded, host reads must come from replicated outputs.
    spmd: bool = False

    def start(self) -> None:  # leader-side channel setup (blocking accept)
        pass

    def emit(self, op: str, args: tuple) -> None:  # broadcast one step
        pass

    def idle(self) -> None:  # liveness beacon hook
        pass

    def run_follower(self, exec_table: Mapping[str, Callable]) -> None:
        raise RuntimeError("this backend has no follower role")

    def stop(self) -> None:  # release followers
        pass

    def close(self) -> None:
        pass


class LocalArraysBackend(DispatchBackend):
    """Single-process arrays (the classic `GenerationEngine` path).
    Every hook is a no-op: `_dx` degenerates to a direct call and the
    engine behaves byte-identically to the pre-dispatch code."""

    spmd = False


class GSPMDBackend(DispatchBackend):
    """Multi-controller leader/follower execution over one global mesh.

    The leader serializes the step-program over the command channel; each
    follower replays it through the SAME op registry the leader executes.
    No scheduling state crosses the wire — only op names and host payloads.
    """

    spmd = True

    def __init__(
        self,
        cmd_addr: str,
        *,
        connect_timeout_s: float = 60.0,
        idle_timeout_s: float = 600.0,
    ):
        self.cmd_addr = cmd_addr
        self.connect_timeout_s = connect_timeout_s
        self.idle_timeout_s = idle_timeout_s
        self._leader: CmdLeader | None = None
        import jax  # deferred: this module stays importable without jax

        self._n_followers = max(jax.process_count() - 1, 0)

    # -- leader side --------------------------------------------------------

    def start(self) -> None:
        if self._leader is None:
            self._leader = CmdLeader(
                self.cmd_addr, self._n_followers, timeout_s=self.connect_timeout_s
            )

    def emit(self, op: str, args: tuple) -> None:
        if self._leader is not None and self._leader.conns:
            self._leader.send(("step", op, args))

    def idle(self) -> None:
        if self._leader is not None and self._leader.conns:
            self._leader.ping_if_idle()

    def stop(self) -> None:
        if self._leader is not None and self._leader.conns:
            try:
                self._leader.send(("stop",))
            except OSError:
                pass

    def close(self) -> None:
        if self._leader is not None:
            self._leader.close()
            self._leader = None

    # -- follower side ------------------------------------------------------

    def run_follower(self, exec_table: Mapping[str, Callable]) -> None:
        """Replay the leader's step-program. This loop is the ENTIRE
        follower: there is deliberately no per-op branching here — an op
        the registry does not know is a protocol error, not a feature."""
        fol = CmdFollower(
            self.cmd_addr,
            timeout_s=self.connect_timeout_s,
            idle_timeout_s=self.idle_timeout_s,
        )
        try:
            while True:
                cmd = fol.recv()
                tag = cmd[0]
                if tag == "ping":
                    continue
                if tag == "stop":
                    return
                if tag != "step":
                    raise ValueError(f"unknown dispatch frame {tag!r}")
                exec_table[cmd[1]](*cmd[2])
        finally:
            fol.close()


# ---------------------------------------------------------------------------
# 2-process demo main (tests/test_dispatch.py spawns it twice): one unified
# engine, GSPMD backend, greedy tokens across the process boundary.
# ---------------------------------------------------------------------------


def _demo_main() -> int:
    n_local = int(os.environ.get("SLICE_LOCAL_DEVICES", "4"))
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_local}"
        ).strip()
    import jax

    if os.environ.get("SLICE_DEMO_CPU", "1") != "0":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from ..parallel import distributed

    multi = distributed.initialize()
    spec = os.environ.get("SLICE_MESH", "dp=4,tp=2")
    mesh = distributed.make_global_mesh(spec)

    from .engine import GenerationEngine

    eng = GenerationEngine(
        os.environ.get("SLICE_MODEL", "tiny-llm"),
        mesh=mesh,
        backend=GSPMDBackend(os.environ["SLICE_CMD_ADDR"]),
        max_slots=int(os.environ.get("SLICE_SLOTS", "8")),
        max_seq_len=int(os.environ.get("SLICE_SEQ", "128")),
        dtype=jnp.float32,
        decode_chunk=4,
    )
    if jax.process_index() == 0:
        eng.start()
        out = eng.generate("dispatch dryrun", max_tokens=6, temperature=0.0)
        n_tok = out["usage"]["completion_tokens"]
        eng.shutdown()
        print(
            f"DISPATCH DEMO OK: {jax.process_count()} processes, "
            f"mesh {spec}, {n_tok} tokens",
            flush=True,
        )
    else:
        eng.run_follower()
        print("DISPATCH FOLLOWER OK", flush=True)
    return 0 if multi or jax.process_count() == 1 else 1


if __name__ == "__main__":
    raise SystemExit(_demo_main())
