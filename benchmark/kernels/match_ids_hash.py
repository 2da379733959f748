"""Operations and bytes of one call of `match_ids_hash`
(emqx_tpu/ops/hash_index.py), from its shapes.

Shapes: B topics (the batch, padded to a power of two), C pattern
classes (`meta.plen.shape[0]`), L levels (`topics.ids.shape[1]`) and H
result slots (`max_hits`).

Bytes, counting what must cross HBM once:
  topics: ids int32 [B, L], lens int32 [B], dollar bool [B]   4BL + 5B
  class meta: plen i32, plus u32, has_hash/active/root_wild bool [C]
                                                              11C
  phase 1: two u32 probe words gathered per (topic, class)    8BC
  phase 2: per result slot, two u32 fingerprints and one
           i32 bucket id gathered                             12H
  results: topic_idx, bucket_id int32 [H], total, amb         8H + 8

Operations, counting 32-bit integer operations:
  phase 1 hashing: per (topic, class, level) a select, an add, two
  xors and three multiplies                                   7BCL
  probe-byte screens of both words, eligibility, the pair count
                                                              20BC
  phase 2: per result slot, 8 lane-byte shifts, masks and compares,
  two argmaxes, two verifies and the index arithmetic         ~100H

The kernel has no matrix unit work; its operations are compared with
the chip's integer peak (peaks.json `int8_ops_per_s`, the only integer
rate the chip's documentation gives), which understates the time they
need, so the bound that binds in practice is the bytes one.
"""

import collections
import re

TRACE_NAMES = ("jit_match_ids_hash",)
PEAK_OPS = "int8_ops_per_s"
# an operand in the HLO text of an op: "s32[64,16]{1,0:T(8,128)} %topics_ids.1"
_OPERAND = re.compile(r"\b\w+\[([\d,]*)\]\{[^}]*\} %(topics_ids|meta_plen|slots_\w+?)(?:\.\d+)?\b")
_S32 = re.compile(r"\bs32\[(\d+)\]")


def cost(shape: dict):
    b, c, lv, h = shape["B"], shape["C"], shape["L"], shape["H"]
    ops = 7 * b * c * lv + 20 * b * c + 100 * h
    nbytes = 4 * b * lv + 5 * b + 11 * c + 8 * b * c + 12 * h + 8 * h + 8
    return ops, nbytes


def shape_of(ops_text: str):
    """A compiled variant's shapes, read from the HLO text of the ops it
    ran (devtrace keeps it per variant): B and L from the `topics_ids`
    operand s32[B,L], C from `meta_plen` s32[C], and H as the most
    frequent other 1-D s32 length (the result buffers and the compacted
    pair indices). None when the text does not name them."""
    dims = {}
    for d, name in _OPERAND.findall(ops_text):
        dims.setdefault(name, tuple(int(x) for x in d.split(",") if x))
    if len(dims.get("topics_ids", ())) != 2 or len(dims.get("meta_plen", ())) != 1:
        return None
    b, lv = dims["topics_ids"]
    (c,) = dims["meta_plen"]
    skip = {b, c} | {v[0] for k, v in dims.items() if k.startswith("slots_") and v}
    counts = collections.Counter(
        int(n) for n in _S32.findall(ops_text) if int(n) not in skip
    )
    if not counts:
        return None
    h = counts.most_common(1)[0][0]
    return {"B": b, "C": c, "L": lv, "H": h}
