"""Exchanges between chips: the all_to_alls' share of the interconnect
roofline, in percent. The bytes of live entries that changed chip (12
for an HDB entry, 8 for a pair word, from the jobs' exchange counts,
``batch_mesh.off_chip_bytes``), per chip, over the all-to-all
operations' device time per chip times one chip's interconnect
bandwidth (``batch_mesh.ICI_BYTES_PER_S``). The buffers carry padding
besides, and the representative exchange of HDB is not counted, so the
share counts only the work that had to move; it cannot pass 100%."""
from bench.harness.batch_mesh import (ALL_TO_ALL, ICI_BYTES_PER_S,
                                      off_chip_bytes, op_seconds)


def read(ctx):
    jobs, tr = ctx.get("jobs"), ctx.get("trace")
    if not jobs or tr is None or not tr["devices"]:
        return None
    exchanges = [ex for r in jobs for ex in (getattr(r, "exchanges", None)
                                             or [])]
    seconds = op_seconds(tr, ALL_TO_ALL)
    if not exchanges or seconds <= 0:
        return None
    per_chip = off_chip_bytes(exchanges) / len(tr["devices"])
    return per_chip / (seconds * ICI_BYTES_PER_S[ctx["device_kind"]]) * 100.0
