"""A light client for POST /v1/generate with an SSE reply (the idea of
tools/bench_serving.py's _sse_generate, not its code). It stamps each read of
the socket and parses nothing while the stream runs, so that hundreds of
frames a second cost the interpreter little; frames are parsed once the
stream has closed."""

import json
import socket
import time


def generate(port, body, timeout, clock=time.monotonic):
    """Returns a dict: status, tokens (list), first, last, end (clock times of
    the reads that brought the first token, the last token and the end of the
    stream), done (the terminal frame) and error."""
    payload = json.dumps(body).encode()
    head = (f"POST /v1/generate HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n").encode()
    out = {"status": None, "tokens": [], "first": None, "last": None,
           "end": None, "done": {}, "error": None}
    reads = []                       # (time, bytes received so far)
    chunks = []
    total = 0
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=timeout) as sock:
            sock.sendall(head + payload)
            while True:
                chunk = sock.recv(65536)
                if not chunk:
                    break
                total += len(chunk)
                chunks.append(chunk)
                reads.append((clock(), total))
    except OSError as e:
        out["error"] = f"{type(e).__name__}: {e}"
    out["end"] = clock()
    raw = b"".join(chunks)
    header, sep, stream = raw.partition(b"\r\n\r\n")
    if not sep:
        out["error"] = out["error"] or "no HTTP header in the reply"
        return out
    try:
        out["status"] = int(header.split(None, 2)[1])
    except (IndexError, ValueError):
        out["error"] = "malformed status line"
        return out
    if out["status"] != 200:
        out["error"] = stream[:200].decode(errors="replace")
        return out
    offset = len(header) + len(sep)
    token_ends = []                  # byte offset in raw at which each token frame ends
    pos = 0
    while True:
        cut = stream.find(b"\n\n", pos)
        if cut < 0:
            break
        frame = stream[pos:cut]
        pos = cut + 2
        is_done = frame.startswith(b"event: done")
        data = frame[frame.find(b"data: ") + 6:]
        try:
            obj = json.loads(data)
        except ValueError:
            out["error"] = "malformed SSE frame"
            return out
        if is_done:
            out["done"] = obj
        else:
            out["tokens"].append(obj["token"])
            token_ends.append(offset + pos)
    if token_ends:
        out["first"] = _arrival(reads, token_ends[0])
        out["last"] = _arrival(reads, token_ends[-1])
    return out


def _arrival(reads, byte_offset):
    """The time of the first read by which `byte_offset` bytes had arrived."""
    for when, total in reads:
        if total >= byte_offset:
            return when
    return reads[-1][0] if reads else None
