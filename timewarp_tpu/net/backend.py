"""Raw socket backends — the "plain socket" under the lively-socket layer.

The reference hard-wires its transport to kernel TCP
(`/root/reference/src/Control/TimeWarp/Rpc/Transfer.hs:406-414` — the
monad stack bottoms out in ``TimedIO``), which is exactly the regression
that cost it network emulation (SURVEY.md "critical historical note").
This build keeps the boundary abstract: the transport talks to a
:class:`RawSocket` / :class:`NetBackend` pair, with two implementations:

- :class:`EmulatedBackend` — an in-memory network fabric driven purely
  by timed effects, so the *whole* transport stack runs under the
  deterministic emulator (and under asyncio, unchanged). Per-link
  latency/loss comes from a :class:`~timewarp_tpu.net.delays.LinkModel`
  sampled with counter-based RNG — reviving the removed
  ``Delays``/``ConnectionOutcome`` surface
  (examples/token-ring/Main.hs:73-77) at the *byte-stream* level.
- :class:`AioBackend` — real kernel TCP via asyncio streams, used by the
  real-IO interpreter through the ``AwaitIO`` effect (≙ the reference's
  ``Network.Socket`` path, Transfer.hs:473, 577).

Semantics shared by both:

- ``send`` never blocks on the wire (the kernel/fabric buffers);
  ordering per direction is FIFO (TCP contract — random per-chunk
  latency is clamped monotone).
- ``recv`` returns ``b""`` on clean EOF; raises
  :class:`~timewarp_tpu.core.errors.SocketBroken` on abrupt break.
- A dropped chunk (link nastiness) breaks the *connection* — TCP never
  silently loses bytes mid-stream — which is what exercises the lively
  socket's reconnect machinery.
"""

from __future__ import annotations

import zlib
from collections import deque
from typing import Any, Deque, Dict, Optional, Tuple

from ..core.effects import AwaitIO, GetTime, Program, Wait
from ..core.errors import ConnectError, SocketBroken
from ..core.time import till
from ..manage.sync import CLOSED, Channel, _Waitable
from .delays import FixedDelay, LinkModel

__all__ = [
    "NetworkAddress", "RawSocket", "NetListener", "NetBackend",
    "EmulatedBackend", "AioBackend", "CLOSED",
]

#: ``(host, port)`` ≙ ``NetworkAddress`` (MonadTransfer.hs:91).
NetworkAddress = Tuple[str, int]


def endpoint_id(name: str) -> int:
    """Stable uint32 id for an endpoint name (``"host:port"``) — feeds
    the counter-based RNG the way node indices do in the batched
    engines, and lets link models address endpoints (e.g. the token-ring
    delays spec giving observer-bound traffic zero latency,
    examples/token-ring/Main.hs:73-77)."""
    return zlib.crc32(name.encode()) & 0xFFFFFFFF


_crc = endpoint_id


class RawSocket:
    """One connected byte-stream endpoint. All methods are programs."""

    peer_addr: str = "?"

    def send(self, data: bytes) -> Program:
        raise NotImplementedError

    def recv(self) -> Program:
        raise NotImplementedError

    def close(self) -> Program:
        raise NotImplementedError


class NetListener:
    """A bound port. ``accept`` blocks; yields back ``(RawSocket, peer)``
    or :data:`CLOSED` once closed."""

    def accept(self) -> Program:
        raise NotImplementedError

    def close(self) -> Program:
        raise NotImplementedError


class NetBackend:
    """Socket factory: ``connect`` + ``bind``."""

    def connect(self, src_host: str, addr: NetworkAddress) -> Program:
        """-> RawSocket; raises :class:`ConnectError`."""
        raise NotImplementedError

    def bind(self, host: str, port: int) -> Program:
        """-> NetListener; raises :class:`ConnectError` if the port is
        taken."""
        raise NotImplementedError


# ----------------------------------------------------------------------
# Emulated fabric
# ----------------------------------------------------------------------

_EOF = object()    # clean FIN
_BREAK = object()  # abrupt reset

#: link-model -> jitted sampler; models are frozen dataclasses
#: (hashable), so equal models share one XLA compilation process-wide
_DRAW_CACHE: Dict[Any, Any] = {}


def _jitted_draw(model: "LinkModel"):
    """Jitted sampler per link model. Falls back to eager per-call
    sampling when the model is unhashable (a user's non-frozen custom
    dataclass cannot key the cache) or its ``sample`` is not traceable
    (e.g. an :class:`FnDelay` written with Python control flow on
    src/dst/t) — slower per draw, but any ``LinkModel`` that works
    eagerly keeps working. Built-in models are frozen dataclasses with
    pure-jnp samplers, so they always take the jitted path."""
    from ..core.rng import msg_bits

    def sample(s0, s1, src, dst, t, slot):
        key = msg_bits(s0, s1, src, dst, t, slot) \
            if model.needs_key else None
        return model.sample(src, dst, t, key)

    try:
        fn = _DRAW_CACHE.get(model)
    except TypeError:           # unhashable user model: never cached
        return sample
    if fn is None:
        import jax
        import jax.numpy as jnp

        try:
            # probe traceability on ABSTRACT avals (no concrete
            # execution): a traceable FnDelay that merely errors on a
            # degenerate concrete (0, 0, 0) probe input must not be
            # silently demoted to the eager per-call path for the
            # whole run — eval_shape only fails when the
            # sampler genuinely cannot trace (Python control flow on
            # src/dst/t, host readbacks, ...)
            u32 = jax.ShapeDtypeStruct((), jnp.uint32)
            jax.eval_shape(sample, u32, u32, u32, u32,
                           jax.ShapeDtypeStruct((), jnp.int64), u32)
            fn = jax.jit(sample)
        except Exception:
            fn = sample
        _DRAW_CACHE[model] = fn
    return fn


class _Pipe(_Waitable):
    """One direction of an emulated connection: a queue of
    ``(deliver_at, payload)`` chunks. Arrival order is send order — the
    per-chunk latency draw is clamped monotone (TCP FIFO contract)."""

    def __init__(self) -> None:
        super().__init__()
        self.chunks: Deque[list] = deque()
        self.last_t = 0

    def push(self, deliver_at: int, payload: Any) -> Program:
        deliver_at = max(deliver_at, self.last_t)
        self.last_t = deliver_at
        self.chunks.append([deliver_at, payload])
        yield from self._notify()

    def pull(self) -> Program:
        """Block until the head chunk's deliver-time; return its payload."""
        while True:
            if self.chunks:
                t = self.chunks[0][0]
                now = yield GetTime()
                if now < t:
                    # FIFO clamp ⇒ the head cannot be superseded while
                    # we sleep; re-check anyway (break may race a close).
                    yield Wait(till(t))
                    continue
                return self.chunks.popleft()[1]
            yield from self._await_change()


class _EmuConn:
    """Shared state of one emulated connection."""

    def __init__(self) -> None:
        self.broken = False


class EmuSocket(RawSocket):
    """Emulated endpoint. Latency/drop sampled per chunk from the
    fabric's link model with ``(src, dst, send_time, chunk_seq)``
    entropy — deterministic under the pure emulator."""

    def __init__(self, fabric: "EmulatedBackend", conn: _EmuConn,
                 local: str, peer: str,
                 in_pipe: _Pipe, out_pipe: _Pipe) -> None:
        self._fabric = fabric
        self._conn = conn
        self.local_addr = local
        self.peer_addr = peer
        self._in = in_pipe
        self._out = out_pipe
        self._src = fabric._eid(local)
        self._dst = fabric._eid(peer)
        self._seq = 0
        self._closed = False

    def send(self, data: bytes) -> Program:
        if self._closed:
            raise SocketBroken(f"socket to {self.peer_addr} is closed")
        if self._conn.broken:
            raise SocketBroken(f"connection to {self.peer_addr} was reset")
        now = yield GetTime()
        delay, drop = self._fabric._sample(self._src, self._dst, now,
                                           self._seq)
        self._seq += 1
        if drop:
            # Nastiness: TCP cannot silently drop bytes mid-stream, so a
            # dropped chunk is a connection reset, surfaced to the
            # sender as a *failed write* — the chunk is NOT delivered
            # and NOT consumed, so the lively socket's pushback +
            # reconnect (Transfer.hs:387-388, 585-603) re-sends it.
            self._conn.broken = True
            yield from self._out.push(now + delay, _BREAK)
            yield from self._in.push(now + delay, _BREAK)
            raise SocketBroken(
                f"connection to {self.peer_addr} was reset")
        yield from self._out.push(now + delay, data)

    def recv(self) -> Program:
        if self._closed:
            return b""
        payload = yield from self._in.pull()
        if payload is _EOF:
            return b""
        if payload is _BREAK:
            raise SocketBroken(f"connection to {self.peer_addr} was reset")
        return payload

    def close(self) -> Program:
        """Clean close: in-flight data still arrives, then the peer sees
        EOF. Idempotent."""
        if self._closed:
            return
        self._closed = True
        if not self._conn.broken:
            # EOF rides behind in-flight chunks (FIFO clamp).
            yield from self._out.push(self._out.last_t, _EOF)
        # wake any local reader blocked in pull
        yield from self._in.push(self._in.last_t, _EOF)


class _EmuListener(NetListener):
    def __init__(self, fabric: "EmulatedBackend", key: NetworkAddress) -> None:
        self._fabric = fabric
        self._key = key
        self._chan: Channel = Channel(64)

    @property
    def closed(self) -> bool:
        return self._chan.closed

    def accept(self) -> Program:
        item = yield from self._chan.get()
        return item  # (EmuSocket, peer_name) or CLOSED

    def close(self) -> Program:
        self._fabric._ports.pop(self._key, None)
        yield from self._chan.close()


class EmulatedBackend(NetBackend):
    """In-memory network fabric (one per scenario). ``delays`` injects
    per-chunk latency and loss; ``connect_delays`` (defaults to the same
    model) governs connection-establishment outcome — a drop there ≙
    the old API's ``NeverConnected``."""

    def __init__(self, delays: Optional[LinkModel] = None, *,
                 connect_delays: Optional[LinkModel] = None,
                 seed: int = 0,
                 endpoint_ids: Optional[Dict[str, int]] = None) -> None:
        from ..core.rng import seed_words
        self._delays = delays if delays is not None else FixedDelay(1000)
        self._cdelays = (connect_delays if connect_delays is not None
                         else self._delays)
        self._s0, self._s1 = seed_words(seed)
        self._ports: Dict[NetworkAddress, _EmuListener] = {}
        self._conn_seq: Dict[Tuple[int, int], int] = {}
        self._ephemeral = 49152
        #: explicit endpoint-name -> id mapping:
        #: lets the fabric feed the link model the SAME ids the
        #: batched world uses (node indices), so one seeded link model
        #: draws identical delays in both worlds; unmapped names
        #: (e.g. ephemeral client ports) keep the crc32 id
        self._endpoint_ids = dict(endpoint_ids or {})
        # warm the sampler compilations NOW: a lazy first-draw compile
        # (~150 ms) inside the asyncio loop would starve ms-scale
        # timers under the real-time interpreter
        for model in {self._delays, self._cdelays}:
            self._draw(model, 0, 0, 0, 0)

    # -- rng -------------------------------------------------------------

    def _eid(self, name: str) -> int:
        """Link-model id of an endpoint name: the explicit mapping when
        declared, the crc32 hash otherwise."""
        mapped = self._endpoint_ids.get(name)
        return mapped if mapped is not None else _crc(name)

    def _draw(self, model: LinkModel, src: int, dst: int, t: int,
              slot: int) -> Tuple[int, bool]:
        """One per-chunk link sample, jit-compiled once per *model*
        (module-scope cache; seeds are runtime args, so every backend
        and every seed shares one compilation): the counter-hash chain
        is ~60 elementwise jnp ops, and dispatching them un-jitted
        costs real wall-clock per chunk — harmless to the virtual clock
        of the pure emulator, but enough to starve ms-scale timers
        under the real-time interpreter."""
        import jax.numpy as jnp
        delay, drop = _jitted_draw(model)(
            jnp.uint32(self._s0), jnp.uint32(self._s1),
            jnp.uint32(src), jnp.uint32(dst),
            jnp.int64(t), jnp.uint32(slot))
        return max(int(delay), 1), bool(drop)

    def _sample(self, src: int, dst: int, t: int,
                slot: int) -> Tuple[int, bool]:
        return self._draw(self._delays, src, dst, t, slot)

    # -- NetBackend ------------------------------------------------------

    def bind(self, host: str, port: int) -> Program:
        key = (host, port)
        if key in self._ports:
            raise ConnectError(f"port {host}:{port} already bound")
        lst = _EmuListener(self, key)
        self._ports[key] = lst
        return lst
        yield  # pragma: no cover — makes this a generator

    def connect(self, src_host: str, addr: NetworkAddress) -> Program:
        self._ephemeral += 1
        local = f"{src_host}:{self._ephemeral}"
        peer = f"{addr[0]}:{addr[1]}"
        src_id, dst_id = self._eid(local), self._eid(peer)
        pair = (_crc(src_host), dst_id)
        slot = self._conn_seq.get(pair, 0)
        self._conn_seq[pair] = slot + 1
        now = yield GetTime()
        delay, drop = self._draw(self._cdelays, src_id, dst_id, now, slot)
        yield Wait(delay)  # connect handshake takes one link latency
        if drop:
            raise ConnectError(f"connect to {peer} dropped by link model")
        lst = self._ports.get(addr)
        if lst is None or lst.closed:
            raise ConnectError(f"connection refused: {peer}")
        conn = _EmuConn()
        a2b, b2a = _Pipe(), _Pipe()
        client = EmuSocket(self, conn, local, peer, in_pipe=b2a, out_pipe=a2b)
        server = EmuSocket(self, conn, peer, local, in_pipe=a2b, out_pipe=b2a)
        status = yield from lst._chan.try_put((server, local))
        if status != "ok":
            raise ConnectError(f"connection refused: {peer} (backlog)")
        return client


# ----------------------------------------------------------------------
# Real TCP via asyncio
# ----------------------------------------------------------------------

class AioSocket(RawSocket):
    """Kernel TCP endpoint (real-IO interpreter only; every operation
    rides the ``AwaitIO`` effect, so ``throw_to`` cancellation works at
    each of them)."""

    def __init__(self, reader: Any, writer: Any, peer: str) -> None:
        self._reader = reader
        self._writer = writer
        self.peer_addr = peer

    def send(self, data: bytes) -> Program:
        try:
            self._writer.write(data)
            yield AwaitIO(self._writer.drain())
        except (ConnectionError, OSError) as e:
            raise SocketBroken(str(e)) from e

    def recv(self) -> Program:
        try:
            data = yield AwaitIO(self._reader.read(65536))
        except (ConnectionError, OSError) as e:
            raise SocketBroken(str(e)) from e
        return data

    def close(self) -> Program:
        import asyncio

        # fd release is synchronous (close survives an aborted cleanup)
        try:
            self._writer.close()
        except (ConnectionError, OSError):
            return

        async def _wait() -> None:
            try:
                await self._writer.wait_closed()
            except (ConnectionError, OSError, asyncio.CancelledError):
                pass

        yield AwaitIO(_wait())


class _AioListener(NetListener):
    def __init__(self, server: Any, queue: Any) -> None:
        self._server = server
        self._queue = queue
        self._closed = False

    def accept(self) -> Program:
        import asyncio
        if self._closed:
            return CLOSED
        get = asyncio.ensure_future(self._queue.get())
        try:
            item = yield AwaitIO(get)
        except BaseException:
            get.cancel()
            raise
        return item

    def close(self) -> Program:
        import asyncio
        import logging

        self._closed = True
        # Resource release is SYNCHRONOUS — if this program is being
        # torn down (GeneratorExit aborts cleanup at the next
        # suspension), the port must still come free: a leaked
        # listening fd would poison the port for the whole process.
        self._server.close()

        def drain() -> None:
            # Close sockets the kernel accepted that no one ever pulled
            # from the accept queue (a connect racing server stop):
            # Python ≥3.12 Server.wait_closed() waits for ALL spawned
            # transports, so one orphaned connection would wedge the
            # stop forever.
            while not self._queue.empty():
                sock, peer = self._queue.get_nowait()
                logging.getLogger("timewarp.comm").debug(
                    "closing never-accepted connection from %s", peer)
                try:
                    sock._writer.close()
                except Exception:  # noqa: BLE001 — best-effort teardown
                    pass

        drain()

        async def _wait() -> None:
            # re-drain after a loop tick: a connection whose
            # connection_made callback was scheduled but had not run at
            # the synchronous drain gets enqueued only now
            await asyncio.sleep(0)
            drain()
            try:
                await asyncio.wait_for(self._server.wait_closed(), 5.0)
            except asyncio.TimeoutError:
                drain()
                logging.getLogger("timewarp.comm").warning(
                    "listener close timed out waiting for spawned "
                    "connections; proceeding")

        yield AwaitIO(_wait())


class AioBackend(NetBackend):
    """Real TCP (≙ ``bindPortTCP``/``getSocketFamilyTCP``,
    Transfer.hs:473, 577)."""

    def connect(self, src_host: str, addr: NetworkAddress) -> Program:
        import asyncio
        try:
            reader, writer = yield AwaitIO(
                asyncio.open_connection(addr[0], addr[1]))
        except (ConnectionError, OSError) as e:
            raise ConnectError(f"connect to {addr[0]}:{addr[1]}: {e}") from e
        return AioSocket(reader, writer, f"{addr[0]}:{addr[1]}")

    def bind(self, host: str, port: int) -> Program:
        import asyncio
        queue: "asyncio.Queue" = asyncio.Queue()

        def on_conn(reader: Any, writer: Any) -> None:
            peer = writer.get_extra_info("peername")
            name = f"{peer[0]}:{peer[1]}" if peer else "?"
            queue.put_nowait((AioSocket(reader, writer, name), name))

        try:
            server = yield AwaitIO(
                asyncio.start_server(on_conn, host=host, port=port))
        except (ConnectionError, OSError) as e:
            raise ConnectError(f"bind {host}:{port}: {e}") from e
        return _AioListener(server, queue)
