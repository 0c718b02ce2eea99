"""MLP up and down FLOPs over the mlp scope's device time at peak (%)."""


def read(run):
    return run.part_mfu.get("mlp")
