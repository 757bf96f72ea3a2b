"""The benchmark's yardstick for requests that share a dispatch: N
overlapping requests through one engine give each request exactly the
answer it would get alone.

That sentence in code. Each request is three columns (public keys, messages,
signatures) in the order its sender sent them; its answer is the bitmap of
its own lanes in that order and whether all of them hold, each lane verified
by the scalar ZIP-215 reference beside this file. No engine, no dedup, no
batching, nothing remembered from one request to the next: a triple that
four requests carry is verified four times. Nothing here imports the program.
"""

from __future__ import annotations

from .ed25519_zip215 import verify_zip215


def answer_alone(pubs, msgs, sigs) -> tuple[bool, list[bool]]:
    """One request's answer: (every lane holds, the bitmap lane for lane).
    A request of no lanes holds nothing: (False, []), as the seam answers it."""
    bits = [verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs, strict=True)]
    return bool(bits) and all(bits), bits


def answers_alone(requests) -> list[tuple[bool, list[bool]]]:
    """The answers of `requests` (each `(pubs, msgs, sigs)`), one by one."""
    return [answer_alone(*r) for r in requests]
