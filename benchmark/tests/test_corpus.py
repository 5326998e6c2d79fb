"""The generators' shape per seed: every seed gives the same sizes, and the
shares the configurations state."""

import json
import os

import numpy as np
import pytest

from benchmark import bgzf
from benchmark.corpus import aligned_pe
from benchmark.references import coordinate_sort as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


#: An exome of the WGS configuration's record shape: reads piled on about
#: 200,000 SureSelect-class capture targets, as an exome configuration
#: would state them.
EXOME = {"capture": {"targets": 200000, "length": [120, 280], "padding": 100,
                     "on_target_share": 0.78, "efficiency_sigma": 0.8},
         "hotspots": {"share": 0.0, "count": 0, "width": 0}, "duplicate_share": 0.15,
         "insert_mean": 220, "insert_sd": 50, "layout_seed": 20240602, "design_seed": 77}


def cfg(name):
    if name == "capture":
        return dict(cfg("wgs30x"), **EXOME)
    return json.load(open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")))


def fields(c):
    s, o = c.stream, c.offsets
    i32 = lambda at: s[at[:, None] + np.arange(4)].copy().view("<i4")[:, 0]  # noqa: E731
    flag = s[o + 18].astype(int) | (s[o + 19].astype(int) << 8)
    return i32(o + 4), i32(o + 8), flag


def n_tags(c):
    """Aux tags per record, walked from each record's tag section."""
    out = []
    s = c.stream
    for o, ln in zip(c.offsets.tolist(), c.lengths.tolist()):
        l_name = int(s[o + 12])
        n_cig = int(s[o + 16]) | int(s[o + 17]) << 8
        l_seq = int.from_bytes(bytes(s[o + 20:o + 24]), "little")
        p = o + 36 + l_name + 4 * n_cig + (l_seq + 1) // 2 + l_seq
        k = 0
        while p < o + ln:
            t = chr(s[p + 2])
            p += 3
            if t == "Z":
                while s[p]:
                    p += 1
                p += 1
            else:
                p += {"C": 1, "c": 1, "S": 2, "s": 2, "I": 4, "i": 4}[t]
            k += 1
        assert p == o + ln
        out.append(k)
    return np.array(out)


@pytest.mark.parametrize("name", ["wgs30x", "capture"])
def test_sizes_do_not_depend_on_the_seed(name):
    a = aligned_pe.generate(cfg(name), 20000, 1)
    b = aligned_pe.generate(cfg(name), 20000, 2**31 + 12345)
    assert np.array_equal(a.lengths, b.lengths)
    assert not np.array_equal(a.stream, b.stream)
    reference.check_chain(a.stream, a.offsets, a.lengths)
    assert 340 <= a.stream.size / a.n_records <= 400


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_wgs_shape(seed):
    c = aligned_pe.generate(cfg("wgs30x"), 20000, seed)
    refid, pos, flag = fields(c)
    unmapped = (flag & 4) != 0
    assert 0.010 <= unmapped.mean() <= 0.020
    tags = n_tags(c)
    both = (flag & 12) == 0  # read and mate mapped
    assert 8 <= tags[both].min() and tags[both].max() <= 10
    assert (tags[both] > 8).mean() > 0.05
    keys = reference.sort_keys(c.stream, c.offsets, c.lengths)
    r1 = ((flag & 64) != 0) & ~unmapped
    # Designed duplicates: pairs that share a place with another pair.
    place = keys[r1]
    dup_share = 1 - len(np.unique(place)) / len(place)
    assert 0.07 <= dup_share <= 0.13
    # Mates share names and sit side by side.
    names = [bytes(c.stream[o + 36:o + 36 + int(c.stream[o + 12])]) for o in c.offsets[:200].tolist()]
    assert all(names[i] == names[i + 1] for i in range(0, 200, 2))


def test_exome_reads_pile_on_targets():
    conf = cfg("capture")
    c = aligned_pe.generate(conf, 20000, 9)
    refid, pos, flag = fields(c)
    design = aligned_pe.Design(conf)
    t_ref, t_pos, t_len, _ = design.targets
    mapped = (flag & 4) == 0
    on = np.zeros(len(pos), bool)
    for r in np.unique(refid[mapped]):
        sel = mapped & (refid == r)
        starts = np.sort(t_pos[t_ref == r] - conf["capture"]["padding"])
        ends = (t_pos + t_len + conf["capture"]["padding"])[t_ref == r][np.argsort(t_pos[t_ref == r])]
        i = np.searchsorted(starts, pos[sel], side="right") - 1
        on[np.flatnonzero(sel)] = (i >= 0) & (pos[sel] < ends[np.maximum(i, 0)])
    assert 0.70 <= on[mapped].mean() <= 0.90


def test_bgzf_round_trip(tmp_path):
    c = aligned_pe.generate(cfg("wgs30x"), 2000, 4)
    head = bgzf.header_block(c.header_text, c.refs)
    path = str(tmp_path / "x.bam")
    w = bgzf.write_bam(path, head, c.stream, c.offsets, c.lengths, 6)
    data = open(path, "rb").read()
    r = bgzf.read_bgzf(data)
    assert r.framing_errors == 0 and r.payload == head + c.stream.tobytes()
    assert w.record_inflated == c.stream.size
    assert bgzf.bam_bytes(head, c.stream, c.offsets, c.lengths, 6) == data
    assert bgzf.record_deflated_bytes(c.stream, c.offsets, c.lengths, 6) == w.record_compressed
    bad = bytearray(data)
    bad[40] ^= 0xFF
    assert bgzf.read_bgzf(bytes(bad)).framing_errors >= 1
    assert bgzf.read_bgzf(data[:-28]).framing_errors == 1
