"""q, k, v and output projection FLOPs over the qkv and attn_out scopes'
device time at peak (%)."""


def read(run):
    return run.part_mfu.get("proj")
