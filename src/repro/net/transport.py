"""Asyncio transport: one :class:`~repro.runtime.node.NodeRuntime` per OS
process, speaking CRC32C frames over TCP or Unix-domain sockets.

The runtime is pure state; this module is the third scheduler that drives it
(after the schedule-randomized Cluster and the timed Simulation).  Effects
map onto the event loop:

* ``SendBytes``  -> the frame enters the per-destination replay queue and a
  dialer task writes it; ``frame`` encodes through the wire codec once.
* ``SetTimer``   -> ``loop.call_later``; staleness is the runtime's
  generation counter, so nothing ever needs cancelling.
* ``EonFlip`` / ``Deliver`` -> surfaced to ``eon_hooks`` / ``deliver_hooks``
  for the harness (acking clients, join barriers).

Channel discipline — the paper assumes FIFO *reliable* channels, and the
chaos proxy deliberately violates raw-TCP reliability (bit flips, truncated
connections), so each directed channel ``src -> dst`` carries its own
exactly-once in-order replay protocol:

* the dialer opens one connection per destination and starts it with a raw
  (un-framed) HELLO preamble — magic, its server id, CRC32C;
* the acceptor replies WELCOME — magic, ``have`` = the count of frames from
  that source it has fully processed, CRC32C — and the dialer replays its
  queue from ``have``;
* the acceptor counts a frame only after the runtime consumed it, scans
  frame boundaries with the codec's extent parser, and feeds the runtime
  whole frames — so a connection that dies mid-frame loses nothing;
* **any** corruption (preamble or frame) surfaces as a typed
  :class:`~repro.wire.errors.WireDecodeError`, tears the connection down,
  resets the runtime's reassembly state, and the replay handshake restores
  the stream: corrupted bytes can delay frames, never mutate or drop them.

Failure detection is the runtime's heartbeat FD (``hb_interval`` /
``hb_timeout`` mapped onto ``SetTimer``): heartbeats ride the same FIFO
channel as protocol traffic, so by the time a timeout fires everything the
dead peer sent first has been processed (Proposition III.14's premise,
within the timeout's slack).

The replay queues are unbounded: a destination that stays unreachable
accumulates frames for the process lifetime.  That is the right trade for a
test/soak transport (hours, not months); a production transport would ack
and prune.
"""
from __future__ import annotations

import asyncio
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..runtime import Deliver, EonFlip, NodeRuntime, SendBytes, SetTimer
from ..wire import crc32c
from ..wire.codec import _frame_extent
from ..wire.errors import WireDecodeError

#: raw (un-framed) connection preamble magic — distinct from the frame
#: magic so a desynchronized stream can never alias a handshake
HELLO_MAGIC = b"ACN+"
HELLO_LEN = 12     # magic(4) | src sid u32be | crc32c(magic+sid) u32be
WELCOME_LEN = 16   # magic(4) | have u64be    | crc32c(magic+have) u32be

#: dialer reconnect backoff (seconds); deliberately short — the chaos proxy
#: tears connections down constantly and the replay handshake is cheap
RECONNECT_DELAY = 0.05
#: handshake stall budget.  Must stay WELL below any heartbeat FD timeout:
#: a live peer's worst-case silence toward a G_R successor is one failed
#: handshake plus one reconnect backoff, and if that exceeds the FD timeout
#: the perfect-failure-detector premise breaks (a live server gets removed).
HANDSHAKE_TIMEOUT = 0.5
READ_CHUNK = 65536


def parse_addr(addr: str) -> Tuple[str, ...]:
    """``"uds:/path/to.sock"`` or ``"tcp:host:port"`` -> parsed tuple."""
    scheme, _, rest = addr.partition(":")
    if scheme == "uds":
        return ("uds", rest)
    if scheme == "tcp":
        host, _, port = rest.rpartition(":")
        return ("tcp", host, int(port))
    raise ValueError(f"bad address {addr!r} (want uds:PATH or tcp:HOST:PORT)")


async def open_connection(addr: str):
    parsed = parse_addr(addr)
    if parsed[0] == "uds":
        return await asyncio.open_unix_connection(parsed[1])
    return await asyncio.open_connection(parsed[1], parsed[2])


async def start_server(addr: str, cb):
    parsed = parse_addr(addr)
    if parsed[0] == "uds":
        return await asyncio.start_unix_server(cb, path=parsed[1])
    return await asyncio.start_server(cb, parsed[1], parsed[2])


class _OutChannel:
    """Replay queue for one directed channel this node dials."""

    __slots__ = ("frames", "wakeup", "task")

    def __init__(self) -> None:
        self.frames: List[bytes] = []    # every frame ever queued, in order
        self.wakeup = asyncio.Event()
        self.task: Optional[asyncio.Task] = None


class NetNode:
    """One process's transport around a :class:`NodeRuntime`.

    ``bind`` is the address this node listens on; ``peers`` maps server id
    -> the address to dial for it (through a chaos proxy, when one fronts
    the peer's listener).  All methods must run on one event loop.
    """

    def __init__(self, runtime: NodeRuntime, *, bind: str,
                 peers: Dict[int, str]):
        self.rt = runtime
        self.sid = runtime.sid
        # FD sizing: a live peer's worst-case silence toward a G_R successor
        # is one failed handshake plus one reconnect backoff.  If the
        # heartbeat timeout doesn't clear that, a live server gets removed
        # and the perfect-failure-detector premise breaks — refuse to start.
        hb_timeout = getattr(runtime, "hb_timeout", None)
        if getattr(runtime, "_hb", False) and hb_timeout is not None:
            if hb_timeout <= HANDSHAKE_TIMEOUT + RECONNECT_DELAY:
                raise ValueError(
                    f"hb_timeout={hb_timeout} must exceed HANDSHAKE_TIMEOUT+"
                    f"RECONNECT_DELAY={HANDSHAKE_TIMEOUT + RECONNECT_DELAY}: "
                    "a reconnecting live peer would be declared dead")
        self.bind = bind
        self.peers = dict(peers)
        self.eon_hooks: List[Callable[[EonFlip], None]] = []
        self.deliver_hooks: List[Callable[[Deliver], None]] = []
        self.reconnects = 0        # dialer reconnections (all causes)
        self.decode_errors = 0     # inbound streams torn down on corruption
        self._out: Dict[int, _OutChannel] = {}
        self._have: Dict[int, int] = {}         # src -> frames fully consumed
        self._rx_conn: Dict[int, Any] = {}      # src -> active inbound writer
        self._server: Optional[asyncio.AbstractServer] = None
        self._stopped = False

    # ---------------------------------------------------------------- lifecycle
    async def start(self, *, boot_server: bool = True) -> None:
        """Open the listener and boot the protocol.  ``boot_server=False``
        for a joiner: its state installs at catch-up (never
        ``server.start()``), but the heartbeat FD still arms."""
        self._server = await start_server(self.bind, self._on_accept)
        if boot_server:
            self.dispatch(self.rt.start())
        else:
            self.dispatch(self.rt.arm_timers())

    async def stop(self) -> None:
        self._stopped = True
        if self._server is not None:
            self._server.close()
        for ch in self._out.values():
            if ch.task is not None:
                ch.task.cancel()
        for w in list(self._rx_conn.values()):
            w.close()
        if self._server is not None:
            # Python >= 3.12 waits here for every accepted connection, so
            # the inbound streams are closed first
            await self._server.wait_closed()
        await asyncio.sleep(0)   # let cancellations unwind

    def pump(self) -> None:
        """Flush effects produced outside an input call (e.g. the harness
        called ``service.submit`` or ``manager.begin_join`` directly)."""
        self.dispatch(self.rt.drain())

    # ----------------------------------------------------------------- effects
    def dispatch(self, effects: List[Any]) -> None:
        loop = asyncio.get_event_loop()
        for e in effects:
            if isinstance(e, SendBytes):
                if e.dst == self.sid:
                    continue   # in-process loopback is not a network hop
                self._queue_frame(e)
            elif isinstance(e, SetTimer):
                loop.call_later(e.delay, self._timer_fired, e.timer_id, e.gen)
            elif isinstance(e, EonFlip):
                for h in self.eon_hooks:
                    h(e)
            elif isinstance(e, Deliver):
                for h in self.deliver_hooks:
                    h(e)

    def _timer_fired(self, timer_id: str, gen: int) -> None:
        if self._stopped:
            return
        self.dispatch(self.rt.on_timer(timer_id, gen))

    def _queue_frame(self, e: SendBytes) -> None:
        ch = self._out.get(e.dst)
        if ch is None:
            ch = self._out[e.dst] = _OutChannel()
            ch.task = asyncio.get_event_loop().create_task(
                self._dialer(e.dst, ch))
        frame = e.frame
        self.rt.record_send(e.dst, e.msg, nbytes=len(frame))
        ch.frames.append(frame)
        ch.wakeup.set()

    # ------------------------------------------------------------------ dialer
    async def _dialer(self, dst: int, ch: _OutChannel) -> None:
        """Own the outbound connection to ``dst`` forever: connect,
        handshake, replay from the peer's ``have``, stream new frames; on
        any error, back off briefly and reconnect."""
        first = True
        while not self._stopped:
            if not first:
                self.reconnects += 1
                await asyncio.sleep(RECONNECT_DELAY)
            first = False
            writer = None
            try:
                addr = self.peers.get(dst)
                if addr is None:
                    return     # unknown peer: nothing to do (stale sends)
                reader, writer = await open_connection(addr)
                hello = HELLO_MAGIC + self.sid.to_bytes(4, "big")
                writer.write(hello + crc32c(hello).to_bytes(4, "big"))
                await writer.drain()
                wel = await asyncio.wait_for(
                    reader.readexactly(WELCOME_LEN), HANDSHAKE_TIMEOUT)
                if (wel[:4] != HELLO_MAGIC
                        or int.from_bytes(wel[12:], "big")
                        != crc32c(wel[:12])):
                    continue   # corrupted welcome: reconnect
                sent = int.from_bytes(wel[4:12], "big")
                if sent > len(ch.frames):
                    continue   # nonsensical (corrupt-but-CRC-valid): retry
                while True:
                    while sent < len(ch.frames):
                        writer.write(ch.frames[sent])
                        sent += 1
                    await writer.drain()
                    ch.wakeup.clear()
                    if sent == len(ch.frames):
                        # wait for new frames, or for the peer to close
                        # (the acceptor never sends after WELCOME, so any
                        # read completion means the connection is dead)
                        waiter = asyncio.ensure_future(ch.wakeup.wait())
                        closer = asyncio.ensure_future(reader.read(1))
                        done, pending = await asyncio.wait(
                            {waiter, closer},
                            return_when=asyncio.FIRST_COMPLETED)
                        for t in pending:
                            t.cancel()
                        for t in (*done, *pending):
                            # retrieve every outcome, else asyncio logs
                            # "Task exception was never retrieved"
                            try:
                                await t
                            except (asyncio.CancelledError, OSError,
                                    EOFError, ConnectionError):
                                pass
                        if closer in done:
                            break   # torn down (chaos or peer restart)
            except asyncio.CancelledError:
                return
            except (OSError, EOFError, asyncio.IncompleteReadError,
                    asyncio.TimeoutError, ConnectionError):
                pass
            finally:
                if writer is not None:
                    writer.close()

    # ---------------------------------------------------------------- acceptor
    async def _on_accept(self, reader, writer) -> None:
        try:
            hello = await asyncio.wait_for(
                reader.readexactly(HELLO_LEN), HANDSHAKE_TIMEOUT)
        except (OSError, asyncio.IncompleteReadError, asyncio.TimeoutError,
                ConnectionError):
            writer.close()
            return
        if (hello[:4] != HELLO_MAGIC
                or int.from_bytes(hello[8:], "big") != crc32c(hello[:8])):
            writer.close()      # corrupted preamble: the dialer will retry
            return
        src = int.from_bytes(hello[4:8], "big")

        old = self._rx_conn.get(src)
        if old is not None:
            old.close()         # a reconnect supersedes the stale stream
        self._rx_conn[src] = writer
        # the dialer replays whole frames from our count, so framing
        # restarts clean regardless of what the dead stream left behind
        self.rt.reset_channel(src)
        wel = HELLO_MAGIC + self._have.get(src, 0).to_bytes(8, "big")
        buf = bytearray()
        try:
            writer.write(wel + crc32c(wel).to_bytes(4, "big"))
            await writer.drain()
            while True:
                data = await reader.read(READ_CHUNK)
                if not data:
                    break
                buf += data
                while True:
                    ext = _frame_extent(buf, 0)
                    if ext is None or len(buf) < ext:
                        break
                    frame = bytes(buf[:ext])
                    del buf[:ext]
                    # feed whole frames only: a teardown mid-frame then
                    # never splits one across reconnects.  The count is
                    # bumped only after the runtime consumed the frame —
                    # the exactly-once guarantee of the replay handshake.
                    self.dispatch(self.rt.on_bytes(src, frame))
                    self._have[src] = self._have.get(src, 0) + 1
        except WireDecodeError:
            # corruption is *detected*, never applied: drop the stream,
            # forget the partial reassembly, let the replay protocol
            # re-deliver from the last fully consumed frame
            self.decode_errors += 1
            self.rt.reset_channel(src)
        except asyncio.CancelledError:
            raise
        except (OSError, ConnectionError):
            pass
        finally:
            if self._rx_conn.get(src) is writer:
                del self._rx_conn[src]
            writer.close()
