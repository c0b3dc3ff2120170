"""The traffic generator: a process of its own that sends tweets as
newline-delimited JSON over localhost TCP to the feed's socket adapter,
on the schedule of its traffic mix, however slow the feed is.

It imports neither JAX nor the program.  It talks to the harness by
lines on stdin and stdout:

    gen -> run   ready                  warm-up and head start prepared
    run -> gen   connect <port>         connect and send the warm-up
    gen -> run   warm <n>               warm-up sent
    run -> gen   go <t0>                window opens at monotonic t0
    run -> gen   stop                   window closed
    gen -> run   report <json>          what was sent, and how late

A backlog mix keeps the socket full until ``stop``.  It makes the rest of
its tweets while the socket is full, and counts it as running dry if the
feed ever took every tweet made ahead while the window was open.  A poisson mix
sends each tweet when it is due, runs its schedule to the end, and
then waits for ``stop``.
Closing the socket ends the feed.

Run by ``bench/run.py``:
    python3 bench/gen.py --traffic FILE --seed N --seconds S --batch B
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import json
import os
import queue
import select
import socket
import sys
import threading
import time
from typing import List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import data, schedule  # noqa: E402

CHUNK = 1024          # tweets formatted and sent together
MAKERS = 4            # processes that make the lines in set-up


def _lines(seed: int, lo: int, hi: int) -> bytes:
    return b"".join(data.json_lines(data.tweets(seed, lo, hi)))


def _chunks(seed: int, lo: int, hi: int) -> List[List[bytes]]:
    """The lines of tweets [lo, hi) in chunks of ``CHUNK``, made by
    ``MAKERS`` processes during set-up."""
    starts = list(range(lo, hi, CHUNK))
    ends = [min(s + CHUNK, hi) for s in starts]
    with concurrent.futures.ProcessPoolExecutor(MAKERS) as ex:
        return list(ex.map(_chunk, [seed] * len(starts), starts, ends,
                           chunksize=8))


def _chunk(seed: int, lo: int, hi: int) -> List[bytes]:
    return data.json_lines(data.tweets(seed, lo, hi))


def _say(msg: str) -> None:
    sys.stdout.write(msg + "\n")
    sys.stdout.flush()


class Generator:
    def __init__(self, traffic: dict, seed: int, seconds: float, batch: int,
                 rate=None):
        self.seed = seed
        self.seconds = seconds
        self.sched = s = schedule.make(traffic, seed, seconds, batch, rate)
        self.warm = _lines(seed, 0, s.warm)
        self.next_id = s.warm
        if s.kind == "poisson":
            # every line of the schedule, made before the run starts
            self.lines = [ln for c in _chunks(seed, s.warm,
                                              s.warm + s.planned)
                          for ln in c]
        else:
            # a head start of whole chunks; the rest is made while the
            # socket is full
            head = -(-s.head // CHUNK) * CHUNK
            self.ready = collections.deque(
                b"".join(c) for c in _chunks(seed, s.warm, s.warm + head))
            self.next_id = s.warm + head
        self.cmds: "queue.Queue[str]" = queue.Queue()
        threading.Thread(target=self._read_cmds, daemon=True).start()

    def _make(self) -> None:
        self.ready.append(_lines(self.seed, self.next_id,
                                 self.next_id + CHUNK))
        self.next_id += CHUNK

    def _read_cmds(self) -> None:
        for line in sys.stdin:
            self.cmds.put(line.strip())
        self.cmds.put("eof")

    def _cmd(self) -> str:
        return self.cmds.get()

    def _stopped(self) -> bool:
        try:
            return self.cmds.get_nowait() in ("stop", "eof")
        except queue.Empty:
            return False

    def run(self) -> None:
        _say("ready")
        cmd = self._cmd()
        if not cmd.startswith("connect "):
            return
        sock = socket.create_connection(("127.0.0.1", int(cmd.split()[1])))
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8 << 20)
        sock.sendall(self.warm)
        _say(f"warm {self.sched.warm}")
        cmd = self._cmd()
        if not cmd.startswith("go "):
            sock.close()
            return
        t0 = float(cmd.split()[1])
        while time.monotonic() < t0:
            time.sleep(max(0.0, min(t0 - time.monotonic(), 0.001)))
        if self.sched.kind == "backlog":
            report = self._backlog(sock, t0)
        else:
            report = self._poisson(sock, t0)
        sock.shutdown(socket.SHUT_WR)
        sock.close()
        _say("report " + json.dumps(report))

    def _backlog(self, sock: socket.socket, t0: float) -> dict:
        """Keep the socket full until stop; whole chunks only, so that
        ``sent`` counts whole tweets."""
        sent = 0
        dry = []
        sock.setblocking(False)
        pending = b""
        stop = False
        while pending or not stop:
            if not pending:
                if not self.ready:
                    # the feed took every tweet made ahead: it may wait
                    t = time.monotonic() - t0
                    if t < self.seconds:
                        dry.append(t)
                    self._make()
                pending = self.ready.popleft()
                sent += CHUNK
            _, writable, _ = select.select([], [sock], [], 0.005)
            if writable:
                pending = pending[sock.send(pending):]
            elif len(self.ready) < 64:
                self._make()
            stop = stop or self._stopped()
        sock.setblocking(True)
        return {"kind": "backlog", "warm": self.sched.warm, "sent": sent,
                "dry_events": len(dry),
                "first_dry_s": dry[0] if dry else None}

    def _poisson(self, sock: socket.socket, t0: float) -> dict:
        """Send each tweet when due; late = send time minus due time."""
        s = self.sched
        offs = s.offsets
        late = np.zeros(s.planned)
        i = 0
        while i < s.planned:
            now = time.monotonic() - t0
            j = int(offs.searchsorted(now, side="right"))
            if j <= i:
                time.sleep(min(offs[i] - now, 0.001))
                continue
            t = time.monotonic() - t0
            sock.sendall(b"".join(self.lines[i:j]))
            late[i:j] = t - offs[i:j]
            i = j
        while not self._stopped():       # the harness closes the window
            time.sleep(0.01)
        n_in = s.due_in_window(self.seconds)
        w = np.sort(late[:n_in])
        return {"kind": "poisson", "warm": s.warm, "sent": s.planned,
                "late_p50_s": float(w[len(w) // 2]),
                "late_p99_s": float(w[min(len(w) - 1, int(0.99 * len(w)))]),
                "late_max_s": float(w[-1])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--rate", type=float, default=None)
    a = ap.parse_args()
    Generator(schedule.load(a.traffic), a.seed, a.seconds, a.batch,
              a.rate).run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
