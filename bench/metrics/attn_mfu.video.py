"""Scores and p @ v FLOPs over the attn scope's device time at peak (%)."""


def read(run):
    return run.part_mfu.get("attn")
