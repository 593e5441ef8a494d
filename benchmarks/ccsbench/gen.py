"""Seeded subread input for a cell: a pool of ZMWs written as BGZF BAM.

A copy of the repo's i.i.d. subread model (``ccsx_tpu/utils/synth.py``
``make_zmw``/``mutate``), kept here so that a later PR cannot change the
yardstick.  ``mutate`` is vectorised: the same per-base model (a base is
deleted with ``del``, else substituted with ``sub``; a kept or
substituted base is followed by a geometric run of uniform insertions,
each with probability ``ins``), drawn from numpy arrays instead of one
Python call per base.

Sizes: every seed gets the same (template length, pass count) in the
same order; the seed draws every base and error.  Template lengths and
polymerase read lengths are read off fixed quantiles of the
configuration's distributions and paired by a fixed permutation (the
polymerase does not know the insert), and a hole's pass count is what
its polymerase read covers: length // (template + adapter).  So runs on
different seeds do the same work and differ in the data alone.  (With
the order seeded too, the batched driver packed each seed's cohort into
slabs differently, and a cohort took 44.8-47.6 s by seed: my chip runs,
PR 22.)
"""

from __future__ import annotations

import dataclasses
import os
import statistics
import struct
import zlib
from typing import List

import numpy as np

ACGT = np.frombuffer(b"ACGT", np.uint8)
# 4-bit BAM codes of A, C, G, T in the =ACMGRSVTWYHKDBN table
NT16 = np.array([1, 2, 4, 8], np.uint8)


@dataclasses.dataclass
class Hole:
    hole: int
    template: np.ndarray          # 2-bit codes
    passes: List[np.ndarray]      # 2-bit codes, oriented as sequenced


def revcomp(codes: np.ndarray) -> np.ndarray:
    return (3 - codes)[::-1]


def mutate(rng: np.random.Generator, seq: np.ndarray, sub: float,
           ins: float, dele: float) -> np.ndarray:
    """One noisy read of ``seq`` (2-bit codes) under the i.i.d. model."""
    n = len(seq)
    r = rng.random(n)
    kept = r >= dele
    is_sub = kept & (r < dele + sub)
    base = seq.copy()
    shift = rng.integers(1, 4, n).astype(np.uint8)
    base[is_sub] = (base[is_sub] + shift[is_sub]) % 4
    # insertions after each kept base: P(k) = ins^k (1 - ins)
    n_ins = np.where(kept, rng.geometric(1.0 - ins, n) - 1, 0)
    width = 1 + n_ins
    out = np.empty(int(width.sum()), np.uint8)
    starts = np.concatenate(([0], np.cumsum(width)[:-1]))
    out[:] = rng.integers(0, 4, len(out)).astype(np.uint8)
    out[starts] = base
    keep = np.ones(len(out), bool)
    keep[starts[~kept]] = False    # a deleted base leaves no slot
    return out[keep]


def size_set(cfg: dict, n: int):
    """The fixed (template length, pass count) list of an n-hole pool:
    quantiles (i + 0.5) / n of the template and polymerase read length
    distributions, paired and ordered by fixed (seed-independent)
    permutations; each hole makes as many passes as its polymerase read
    covers template plus adapter."""
    t = cfg["template_len"]
    pol = cfg["polymerase_len"]
    q = [(i + 0.5) / n for i in range(n)]
    tlens = [int(round(t["lo"] + (t["hi"] - t["lo"]) * x)) for x in q]
    z = statistics.NormalDist()
    reads = [pol["median"] * float(np.exp(pol["sigma"] * z.inv_cdf(x)))
             for x in q]
    pair = np.random.default_rng(0).permutation(n)
    order = np.random.default_rng(1).permutation(n)
    sizes = [(tlen, max(1, int(reads[int(pair[i])]
                               // (tlen + cfg["adapter_len"]))))
             for i, tlen in enumerate(tlens)]
    return [sizes[int(i)] for i in order]


def make_pool(cfg: dict, n: int, seed: int) -> List[Hole]:
    """``n`` seeded holes of the configuration, in the fixed order of
    ``size_set``."""
    err = cfg["error_model"]
    rng = np.random.default_rng(seed)
    sizes = size_set(cfg, n)
    holes = []
    for h, (tlen, count) in enumerate(sizes):
        template = rng.integers(0, 4, tlen).astype(np.uint8)
        first = int(rng.integers(2))
        passes = []
        for k in range(count):
            read = mutate(rng, template, err["sub"], err["ins"], err["del"])
            if (first + k) % 2:
                read = revcomp(read)
            if err.get("partial_ends") and count >= 5 \
                    and k in (0, count - 1):
                keep = max(int(len(read) * (0.3 + 0.3 * rng.random())), 50)
                read = read[-keep:] if k == 0 else read[:keep]
            passes.append(read)
        holes.append(Hole(h, template, passes))
    return holes


def subread_names(movie: str, hole: Hole) -> List[str]:
    out, off = [], 0
    for p in hole.passes:
        out.append(f"{movie}/{hole.hole}/{off}_{off + len(p)}")
        off += len(p)
    return out


def _bam_record(name: str, codes: np.ndarray) -> bytes:
    nm = name.encode() + b"\x00"
    n = len(codes)
    nib = NT16[codes]
    if n % 2:
        nib = np.concatenate((nib, np.zeros(1, np.uint8)))
    packed = (nib[0::2] << 4) | nib[1::2]
    body = struct.pack("<iiBBHHHiiii", -1, -1, len(nm), 255, 0, 0, 4, n,
                       -1, -1, 0)
    body += nm + packed.tobytes() + b"\xff" * n
    return struct.pack("<i", len(body)) + body


_BGZF_EOF = bytes.fromhex("1f8b08040000000000ff0600424302001b00"
                          "03000000000000000000")


def _bgzf_block(data: bytes) -> bytes:
    c = zlib.compressobj(1, zlib.DEFLATED, -15)
    comp = c.compress(data) + c.flush()
    head = struct.pack("<BBBBIBBHBBHH", 31, 139, 8, 4, 0, 0, 255, 6,
                       66, 67, 2, len(comp) + 25)
    tail = struct.pack("<II", zlib.crc32(data) & 0xFFFFFFFF, len(data))
    return head + comp + tail


def write_bam(path: str, movie: str, holes: List[Hole]) -> None:
    """Unaligned subreads BAM in BGZF blocks, written to ``path`` via a
    temporary name (a cut run leaves no half file behind)."""
    text = b"@HD\tVN:1.6\tSO:unknown\n"
    raw = [b"BAM\x01", struct.pack("<i", len(text)), text,
           struct.pack("<i", 0)]
    for h in holes:
        for name, p in zip(subread_names(movie, h), h.passes):
            raw.append(_bam_record(name, p))
    data = b"".join(raw)
    tmp = path + ".part"
    with open(tmp, "wb") as f:
        for i in range(0, len(data), 65280):
            f.write(_bgzf_block(data[i:i + 65280]))
        f.write(_BGZF_EOF)
    os.replace(tmp, path)
