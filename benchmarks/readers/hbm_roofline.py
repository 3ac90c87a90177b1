"""The fused step's share of the HBM roofline, in %: the bytes the
algorithm needs for the operations that rode a device round inside the
traced window (``needs.py``, from the deployment's shapes), over the
chip's peak bytes per second (``peaks.json``), over the seconds the
device was busy in that window.  Memory-bound by construction: the step
is gathers, compares and scatters, no matrix product.

Device operations = served operations less the reads the mirror
answered; device reads = fast-path misses; the rest are writes."""

import json
import os

import needs


def read(facts):
    tr = facts["dump"].get("trace") or {}
    red, c = tr.get("reduction"), tr.get("counters")
    if not red or red["busy_s"] <= 0:
        return None
    with open(os.path.join(facts["here"], "peaks.json")) as f:
        peaks = json.load(f)
    kind = facts["dump"]["device_kind"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    cfg = facts["cfg"]
    reads = max(c["read_fastpath_misses"], 0)
    writes = max(c["ops_served"] - c["read_fastpath_hits"] - reads, 0)
    need = (writes * needs.write_bytes(cfg["n_peers"], cfg["n_slots"])
            + reads * needs.read_bytes(cfg["n_peers"], cfg["n_slots"]))
    least_s = need / peaks[kind]["hbm_bytes_per_s"] / red["devices"]
    return 100.0 * least_s / red["busy_s"], reads + writes
