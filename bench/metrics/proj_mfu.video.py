"""q, k, v and output projection FLOPs over the qkv and attn_out scopes'
device time at peak (%), leaving out the ops under ``qk_prep`` (the q/k
LayerNorm and 3-D RoPE inside ``qkv``: elementwise work that the
projections' FLOPs do not count).  Those ops count as ``other``."""
import dataclasses

from bench import readers

SKIP = "qk_prep"


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    paths = [{op: ("" if SKIP in path.split("/") else path)
              for op, path in chip.items()} for chip in tr.paths]
    return readers.part_mfu(dataclasses.replace(tr, paths=paths),
                            run.cell.config,
                            run.peak["bf16_flops_per_s"]).get("proj")
