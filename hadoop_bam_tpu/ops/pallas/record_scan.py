"""Lockstep-lane Pallas FASTQ record-boundary scanner.

The fourth client of the lockstep-lane engine: up to 128 decoded FASTQ
chunks ride the 128 vector lanes of one kernel, each advancing its own
byte-wave line/frame state machine.  This vectorizes the split-guesser
pattern from ``io/fastq.py`` — find ``@``-record starts with the
4-line / plus-line / quality-length consistency check so a ``@`` inside
a quality string never splits a record — at device speed over payloads
that just came off the inflate lanes.

Wave model: global wave ``t`` consumes one byte per lane (4 wave-bytes
packed per int32 word, per the engine house style).  Each lane keeps a
packed register file in VMEM scratch — current-line accumulators, an
8-deep completed-line history (first byte, CR-stripped length, start
offset), sync/frame state — and every per-lane update is a dense
iota-compare column select, never a gather.

Resync is the **two-consecutive-verified-records** rule (the BGZF
split-guesser stance, shared with
``FastqInputFormat.position_at_first_record``): an ``@`` line is
trusted as a record start only when the 8-line history forms two
back-to-back frames ``(@, seq, +, qual)`` with ``len(seq) == len(qual)``
in both.  Aligned lanes (a chunk that starts exactly at a record start)
skip resync and validate every frame as it completes.

Claim protocol: lane ``k`` owns records *starting* inside its claim
region ``[0, chunk_len)``; the window extends ``overlap`` bytes past the
claim so the tail record can complete.  A record starting at or past
``chunk_len`` belongs to the next lane and halts the scan (``done``).

Per-lane ``[n_records, ok]`` meta tiers a chunk that cannot sync, hits a
frame violation, overflows the record tile, or leaves a claimed record
unfinished down to the host tiers *per chunk, never per launch*:
``scan_window_host`` (vectorized NumPy, the semantic reference) and
``scan_window_py`` (the plain Python walker oracle, which also carries
the ``errors=salvage`` quarantine semantics).  Tests run the kernel in
interpret mode on CPU and compare record tables bit-for-bit.

Record rows: each record is 8 int32s
``[id_start, id_len, seq_start, seq_len, plus_start, plus_len,
qual_start, qual_len]`` — offsets window-relative, lengths CR-stripped
(CRLF input parses identically to LF across all tiers).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.backend import pallas_interpret

from ...spec.fragment import FormatException

LANES = 128

#: VMEM budget for one launch (window bank + record tile + state).
_VMEM_BUDGET_BYTES = 14 << 20

#: Window cap per lane (bytes); chunks re-chunked at or below the
#: device inflate payload stay far under this.
_MAX_WINDOW = 1 << 17

_AT = 0x40     # '@'
_PLUS = 0x2B   # '+'
_NL = 0x0A
_CR = 0x0D

# Packed per-lane register rows in the ``st`` scratch bank.
_S_LEN = 0      # raw byte count of the current line (newline excluded)
_S_FIRST = 1    # first byte of the current line, -1 while empty
_S_START = 2    # window offset of the current line start
_S_LAST = 3     # last byte seen on the current line, -1 while empty
_S_LC = 4       # completed-line count
_S_SYNC = 5     # 1 once the frame phase is locked
_S_BASE = 6     # line index of the first locked record start
_S_NREC = 7     # claimed records emitted
_S_OK = 8       # 1 until a tier-down condition fires
_S_DONE = 9     # 1 once the first beyond-claim record start is seen
_H_FC = 10      # rows 10..17: first byte of the last 8 lines
_H_LN = 18      # rows 18..25: CR-stripped length of the last 8 lines
_H_ST = 26      # rows 26..33: window offset of the last 8 lines
_ST_ROWS = 40

_REC_W = 8


class WindowOverrun(Exception):
    """A claimed record does not finish inside the scan window; the
    caller rescans the whole run serially (bigger effective window)."""


@dataclass
class RecordScanStats:
    """Where each chunk of a scan went, and why the fallen fell."""

    lanes: int = 0            # chunks fully scanned on the lanes
    host: int = 0             # chunks rescued by the host tiers
    launches: int = 0
    reasons: Dict[str, int] = field(default_factory=dict)

    def tier_down(self, reason: str) -> None:
        self.host += 1
        self.reasons[reason] = self.reasons.get(reason, 0) + 1


def scan_geometry(max_window: int, rec_cap: int) -> Tuple[int, int]:
    """Static launch geometry: input words per lane (4 bytes packed per
    int32, padded to a 256-word step) and the record-tile row count."""
    n_words = max(256, -(-max_window // 4))
    n_words = -(-n_words // 256) * 256
    return n_words, _REC_W * rec_cap


def accepts(max_window: int, rec_cap: int) -> Tuple[bool, str]:
    """Geometry gate for one launch group; reasons feed the tier-down
    taxonomy (``size`` / ``vmem``)."""
    if max_window > _MAX_WINDOW:
        return False, "size"
    n_words, rec_rows = scan_geometry(max_window, rec_cap)
    vmem = (n_words + rec_rows + _ST_ROWS + 8) * LANES * 4
    if vmem > _VMEM_BUDGET_BYTES:
        return False, "vmem"
    return True, ""


def default_rec_cap(max_window: int) -> int:
    """Record-tile capacity for a window: the 6-byte minimum record
    bounds the count, clamped so the tile stays inside the VMEM budget
    (an overflowing lane tiers down with reason ``records``)."""
    cap = max_window // 6 + 2
    n_words, _ = scan_geometry(max_window, 1)
    budget_rows = _VMEM_BUDGET_BYTES // (LANES * 4) - n_words - _ST_ROWS - 8
    cap = min(cap, max(8, budget_rows // _REC_W))
    return -(-cap // 64) * 64


def _kernel_factory(n_words: int, rec_cap: int):
    rec_rows = _REC_W * rec_cap

    def kernel(meta_ref, words_ref, recs_ref, mout_ref, st_ref):
        rows_st = lax.broadcasted_iota(jnp.int32, (_ST_ROWS, LANES), 0)
        rows_rec = lax.broadcasted_iota(jnp.int32, (rec_rows, LANES), 0)
        chunk_len = meta_ref[0, :]
        win_len = meta_ref[1, :]
        aligned = meta_ref[2, :]
        final = meta_ref[3, :]

        def row(st, r):
            return jnp.sum(jnp.where(rows_st == r, st, 0), axis=0)

        def put(st, r, val):
            return jnp.where(rows_st == r, val, st)

        # Register-file init: empty line accumulators, history of
        # impossible lines, sync pre-locked on aligned lanes.
        st0 = jnp.zeros((_ST_ROWS, LANES), jnp.int32)
        st0 = put(st0, _S_FIRST, jnp.full((LANES,), -1, jnp.int32))
        st0 = put(st0, _S_LAST, jnp.full((LANES,), -1, jnp.int32))
        st0 = put(st0, _S_OK, jnp.ones((LANES,), jnp.int32))
        st0 = put(st0, _S_SYNC, aligned)
        for i in range(8):
            st0 = put(st0, _H_FC + i, jnp.full((LANES,), -1, jnp.int32))

        hist_mask = (rows_st >= _H_FC) & (rows_st < _H_ST + 8)

        def complete_line(st, recs, live, t_next):
            """One newline (real or synthetic) on the lanes in ``live``:
            push the finished line into history, attempt sync, emit and
            validate claimed frames, reset the line accumulators."""
            cur_len = row(st, _S_LEN)
            cur_first = row(st, _S_FIRST)
            cur_start = row(st, _S_START)
            cur_last = row(st, _S_LAST)
            eff = cur_len - (cur_last == _CR).astype(jnp.int32)

            rolled = jnp.concatenate([st[1:], st[:1]], axis=0)
            st = jnp.where(hist_mask & live, rolled, st)
            st = put(st, _H_FC + 7, jnp.where(live, cur_first, row(st, _H_FC + 7)))
            st = put(st, _H_LN + 7, jnp.where(live, eff, row(st, _H_LN + 7)))
            st = put(st, _H_ST + 7, jnp.where(live, cur_start, row(st, _H_ST + 7)))

            lc = row(st, _S_LC) + live.astype(jnp.int32)
            st = put(st, _S_LC, lc)

            fc = [row(st, _H_FC + i) for i in range(8)]
            ln = [row(st, _H_LN + i) for i in range(8)]
            stt = [row(st, _H_ST + i) for i in range(8)]

            synced = row(st, _S_SYNC)
            base = row(st, _S_BASE)
            nrec = row(st, _S_NREC)
            ok = row(st, _S_OK)
            done = row(st, _S_DONE)

            frame_a = (fc[0] == _AT) & (fc[2] == _PLUS) & (ln[1] == ln[3])
            frame_b = (fc[4] == _AT) & (fc[6] == _PLUS) & (ln[5] == ln[7])
            can_sync = live & (synced == 0) & (lc >= 8) & frame_a & frame_b
            sync_claim = can_sync & (stt[0] < chunk_len)
            sync_beyond = can_sync & (stt[0] >= chunk_len)

            bnd = live & (synced == 1) & (((lc - base) & 3) == 0)
            claim_b = stt[4] < chunk_len
            emit2 = (bnd | sync_claim) & claim_b & frame_b
            bad = bnd & claim_b & (~frame_b)
            done_now = ((bnd | sync_claim) & (~claim_b)) | sync_beyond

            n_emits = sync_claim.astype(jnp.int32) + emit2.astype(jnp.int32)
            over = (nrec + n_emits) > rec_cap
            good = (ok == 1) & (~over)
            do1 = sync_claim & good
            do2 = emit2 & good

            vals1 = [stt[0], ln[0], stt[1], ln[1], stt[2], ln[2], stt[3], ln[3]]
            for s in range(_REC_W):
                tgt = _REC_W * nrec + s
                recs = jnp.where((rows_rec == tgt) & do1, vals1[s], recs)
            nrec1 = nrec + do1.astype(jnp.int32)
            vals2 = [stt[4], ln[4], stt[5], ln[5], stt[6], ln[6], stt[7], ln[7]]
            for s in range(_REC_W):
                tgt = _REC_W * nrec1 + s
                recs = jnp.where((rows_rec == tgt) & do2, vals2[s], recs)

            st = put(st, _S_NREC, nrec1 + do2.astype(jnp.int32))
            st = put(st, _S_OK, jnp.where(bad | (live & over), 0, ok))
            st = put(st, _S_DONE, jnp.where(done_now, 1, done))
            st = put(st, _S_SYNC, jnp.where(sync_claim, 1, synced))
            st = put(st, _S_BASE, jnp.where(sync_claim, lc - 8, base))

            st = put(st, _S_LEN, jnp.where(live, 0, row(st, _S_LEN)))
            st = put(st, _S_FIRST, jnp.where(live, -1, row(st, _S_FIRST)))
            st = put(st, _S_LAST, jnp.where(live, -1, row(st, _S_LAST)))
            st = put(st, _S_START, jnp.where(live, t_next, row(st, _S_START)))
            return st, recs

        words = words_ref[:, :]

        def body(w, carry):
            st, recs = carry
            word = lax.dynamic_index_in_dim(words, w, 0, keepdims=False)
            for jj in range(4):
                byte = (word >> (8 * jj)) & 0xFF
                t = w * 4 + jj
                live = (t < win_len) & (row(st, _S_OK) == 1) \
                    & (row(st, _S_DONE) == 0)
                is_nl = live & (byte == _NL)
                txt = live & (byte != _NL)
                cur_first = row(st, _S_FIRST)
                st = put(st, _S_FIRST,
                         jnp.where(txt & (cur_first < 0), byte, cur_first))
                st = put(st, _S_LAST,
                         jnp.where(txt, byte, row(st, _S_LAST)))
                st = put(st, _S_LEN, row(st, _S_LEN) + txt.astype(jnp.int32))
                st, recs = complete_line(st, recs, is_nl, t + 1)
            return st, recs

        st = st0
        recs0 = jnp.zeros((rec_rows, LANES), jnp.int32)
        st, recs = lax.fori_loop(0, n_words, body, (st, recs0))

        # Synthetic final newline: end-of-run text without a trailing
        # '\n' still completes its last line, as in the host walker.
        tail = (final == 1) & (row(st, _S_LEN) > 0) \
            & (row(st, _S_OK) == 1) & (row(st, _S_DONE) == 0)
        st, recs = complete_line(st, recs, tail, win_len)

        # Final verdicts.  A claimed record left unfinished (partial
        # frame, or dangling text on a non-final window) and a lane
        # that never synced over real content both tier down.
        synced = row(st, _S_SYNC)
        lc = row(st, _S_LC)
        base = row(st, _S_BASE)
        done = row(st, _S_DONE)
        ok = row(st, _S_OK)
        pend = (lc - base) & 3
        part_start = jnp.zeros((LANES,), jnp.int32)
        for i in range(8):
            part_start = jnp.where(8 - pend == i, row(st, _H_ST + i),
                                   part_start)
        bad_tail = (synced == 1) & (done == 0) & (pend != 0) \
            & (part_start < chunk_len)
        bad_text = (done == 0) & (row(st, _S_LEN) > 0) \
            & (row(st, _S_START) < chunk_len)
        bad_sync = (synced == 0) & (done == 0) \
            & ((lc > 0) | (row(st, _S_LEN) > 0))
        ok = jnp.where(bad_tail | bad_text | bad_sync, 0, ok)

        recs_ref[:, :] = recs
        mout_ref[:, :] = jnp.stack([row(st, _S_NREC), ok], axis=0)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("n_words", "rec_cap", "interpret")
)
def _record_scan_kernel(meta, words, n_words: int, rec_cap: int, interpret: bool):
    kernel = _kernel_factory(n_words, rec_cap)
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((_REC_W * rec_cap, LANES), jnp.int32),
            jax.ShapeDtypeStruct((2, LANES), jnp.int32),
        ),
        scratch_shapes=[pltpu.VMEM((_ST_ROWS, LANES), jnp.int32)],
        interpret=interpret,
    )(meta, words)


def _pack_windows(group, n_words):
    """Windows into the transposed [n_words, LANES] int32 word bank."""
    bank = np.zeros((n_words * 4, LANES), np.uint8)
    meta = np.zeros((4, LANES), np.int32)
    for lane, (_, win, chunk_len, algn, fin) in enumerate(group):
        bank[: len(win), lane] = np.frombuffer(win, np.uint8)
        meta[0, lane] = chunk_len
        meta[1, lane] = len(win)
        meta[2, lane] = 1 if algn else 0
        meta[3, lane] = 1 if fin else 0
    words = (
        bank.reshape(n_words, 4, LANES).astype(np.int32)
        * (1 << (8 * np.arange(4, dtype=np.int32)))[None, :, None]
    ).sum(axis=1, dtype=np.int32)
    return meta, words


def record_scan(
    chunks: Sequence[Tuple[bytes, int, bool, bool]],
    rec_cap: Optional[int] = None,
    interpret=None,
) -> Tuple[List[Optional[np.ndarray]], RecordScanStats]:
    """Batched lockstep record-boundary scan, up to 128 chunks per
    launch.  ``chunks`` entries are ``(window, chunk_len, aligned,
    final)`` — the window is the claim region plus overlap.

    Returns ``(tables, stats)``: per-chunk ``[n, 8]`` int32 record
    tables with ``None`` for every chunk that tiered down (the caller
    rescues those through :func:`scan_window_host` and the walker) plus
    the tier taxonomy.  Tier-down is per chunk, never per launch."""
    interpret = pallas_interpret(interpret)
    stats = RecordScanStats()
    B = len(chunks)
    outs: List[Optional[np.ndarray]] = [None] * B
    accepted = []
    for i, (win, chunk_len, algn, fin) in enumerate(chunks):
        if len(win) > _MAX_WINDOW:
            stats.tier_down("size")
            continue
        accepted.append((i, bytes(win), int(chunk_len), bool(algn),
                         bool(fin)))
    for g0 in range(0, len(accepted), LANES):
        group = accepted[g0: g0 + LANES]
        max_win = max(len(win) for _, win, _, _, _ in group)
        cap = rec_cap if rec_cap is not None else default_rec_cap(max_win)
        okg, reason = accepts(max_win, cap)
        if not okg:
            for _ in group:
                stats.tier_down(reason)
            continue
        n_words, _ = scan_geometry(max_win, cap)
        meta, words = _pack_windows(group, n_words)
        recs, mout = _record_scan_kernel(
            jnp.asarray(meta), jnp.asarray(words),
            n_words=n_words, rec_cap=cap, interpret=bool(interpret),
        )
        recs = np.asarray(recs)
        mout = np.asarray(mout)
        stats.launches += 1
        for lane, (i, win, chunk_len, _, _) in enumerate(group):
            n, lane_ok = int(mout[0, lane]), int(mout[1, lane])
            if not lane_ok:
                stats.tier_down("scan")
                continue
            stats.lanes += 1
            outs[i] = (
                recs[: _REC_W * n, lane]
                .reshape(n, _REC_W).astype(np.int32, copy=True)
            )
    return outs, stats


# ---------------------------------------------------------------------------
# Host tiers: the NumPy scan is the semantic reference the kernel must
# match bit-for-bit where it reports ok; the Python walker beneath it is
# the oracle and carries the salvage quarantine semantics.

def _line_table_np(win: np.ndarray, final: bool):
    """Completed lines of a window: (starts, first raw byte or -1,
    CR-stripped lengths, unterminated tail start or -1).  On a final
    window the unterminated tail counts as a last line, exactly as the
    kernel's synthetic final newline."""
    nl = np.flatnonzero(win == _NL)
    starts = np.concatenate(([0], nl + 1)).astype(np.int64)
    tail_start = int(starts[-1]) if starts[-1] < len(win) else -1
    if final and tail_start >= 0:
        ends = np.concatenate((nl, [len(win)])).astype(np.int64)
        tail_start = -1
    else:
        ends = nl.astype(np.int64)
    starts = starts[: len(ends)]
    raw = ends - starts
    eff = raw.copy()
    if len(ends):
        has_cr = (raw > 0) & (win[np.maximum(ends - 1, 0)] == _CR)
        eff = raw - has_cr.astype(np.int64)
    fc = np.full(len(starts), -1, np.int64)
    if len(starts):
        nonempty = raw > 0
        fc[nonempty] = win[starts[nonempty]]
    return starts, fc, eff, tail_start


def scan_window_host(
    win, chunk_len: int, aligned: bool, final: bool
) -> np.ndarray:
    """Vectorized NumPy record scan of one window; the semantic
    reference for the kernel tier (bit-exact where the kernel reports
    ``ok``).  Raises :class:`FormatException` on a frame violation or a
    truncated claimed record, and :class:`WindowOverrun` when a claimed
    record runs past a non-final window (the caller widens by rescanning
    the whole run serially)."""
    win = np.frombuffer(bytes(win), np.uint8)
    if len(win) == 0:
        return np.zeros((0, _REC_W), np.int32)
    starts, fc, eff, tail_start = _line_table_np(win, final)
    nlines = len(starts)

    # frame[i]: lines i..i+3 form one (@, seq, +, qual) frame.
    frame = np.zeros(nlines, bool)
    if nlines >= 4:
        frame[: nlines - 3] = (
            (fc[: nlines - 3] == _AT) & (fc[2: nlines - 1] == _PLUS)
            & (eff[1: nlines - 2] == eff[3: nlines])
        )

    if aligned:
        l0 = 0
    else:
        # Two-consecutive-verified-records rule, with the end-of-data
        # relaxation (a final window trusts a lone trailing frame —
        # the stance shared with position_at_first_record).
        ver = np.zeros(nlines, bool)
        if nlines >= 8:
            ver[: nlines - 7] = frame[: nlines - 7] & frame[4: nlines - 3]
        if final and nlines >= 4:
            lo = max(0, nlines - 7)
            ver[lo: nlines - 3] |= frame[lo: nlines - 3]
        cand = np.flatnonzero(ver)
        if len(cand) == 0 or starts[int(cand[0])] >= chunk_len:
            # No trusted record start inside the claim: either the
            # window is the tail of the previous lane's record, or it is
            # garbage — the caller's run-tiling reconciliation tells the
            # two apart and rescans serially on a gap.
            return np.zeros((0, _REC_W), np.int32)
        l0 = int(cand[0])

    recs = []
    li = l0
    while li < nlines and starts[li] < chunk_len:
        if li + 3 >= nlines:
            if final:
                raise FormatException(
                    "fastq: truncated record at end of input"
                )
            raise WindowOverrun("fastq: claimed record overruns window")
        if not frame[li]:
            raise FormatException(
                "fastq: frame violation at offset %d" % starts[li]
            )
        recs.append([
            starts[li], eff[li], starts[li + 1], eff[li + 1],
            starts[li + 2], eff[li + 2], starts[li + 3], eff[li + 3],
        ])
        li += 4
    if tail_start >= 0 and tail_start < chunk_len and li >= nlines:
        raise WindowOverrun("fastq: claimed record overruns window")
    return np.asarray(recs, np.int32).reshape(len(recs), _REC_W)


def scan_window_py(
    win, chunk_len: int, aligned: bool, final: bool, salvage: bool = False
) -> Tuple[np.ndarray, int]:
    """Plain-Python walker: the oracle beneath the NumPy tier, one line
    at a time.  With ``salvage=True`` a frame violation or truncated
    claimed tail quarantines whole 4-line frames (never tearing one) and
    resyncs with the two-record rule; returns ``(records,
    n_quarantine_events)``."""
    win = bytes(win)
    lines = []       # (start, first byte or -1, eff len)
    pos = 0
    while pos < len(win):
        nl = win.find(b"\n", pos)
        if nl < 0:
            if not final:
                break
            nl = len(win)
        raw = nl - pos
        eff = raw - (1 if raw and win[nl - 1: nl] == b"\r" else 0)
        lines.append((pos, win[pos] if raw else -1, eff))
        pos = nl + 1
    tail_start = pos if pos < len(win) else -1
    n_quar = 0

    def frame_at(i):
        """True/False for a complete 4-line frame at ``i``; None when
        fewer than 4 lines remain."""
        if i + 3 >= len(lines):
            return None
        return (lines[i][1] == _AT and lines[i + 2][1] == _PLUS
                and lines[i + 1][2] == lines[i + 3][2])

    def sync_from(i0):
        for i in range(i0, len(lines)):
            fa = frame_at(i)
            if fa is None:
                break
            if not fa:
                continue
            fb = frame_at(i + 4)
            if not (fb or (fb is None and final)):
                continue
            if lines[i][0] >= chunk_len:
                return None   # first trusted start belongs to the next lane
            return i
        return None   # no trusted start: previous lane's tail, or garbage
                      # (the caller's run-tiling reconciliation decides)

    recs = []
    li = 0 if aligned else sync_from(0)
    while li is not None and li < len(lines) and lines[li][0] < chunk_len:
        fr = frame_at(li)
        if fr:
            s = lines[li: li + 4]
            recs.append([s[0][0], s[0][2], s[1][0], s[1][2],
                         s[2][0], s[2][2], s[3][0], s[3][2]])
            li += 4
            continue
        if fr is None and not final:
            raise WindowOverrun("fastq: claimed record overruns window")
        if not salvage:
            raise FormatException(
                "fastq: %s at offset %d" % (
                    "truncated record" if fr is None else "frame violation",
                    lines[li][0],
                )
            )
        n_quar += 1
        if fr is None:
            li = None
            break
        try:
            li = sync_from(li + 1)
        except (FormatException, WindowOverrun):
            li = None
    if tail_start >= 0 and tail_start < chunk_len \
            and li is not None and li >= len(lines):
        raise WindowOverrun("fastq: claimed record overruns window")
    return (np.asarray(recs, np.int32).reshape(len(recs), _REC_W), n_quar)
