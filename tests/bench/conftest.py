"""Tiny cells for driving the benchmark harness on the CPU."""
import pytest

from bench import spec


def tiny_cell(chips: int = 1, dtype: str = "float32",
              sp: dict | None = None, guidance: float = 1.0,
              check_requests: int = 100, limit: float = 1e-3) -> spec.Cell:
    """A cell at toy widths, read by the harness's own metric files."""
    config = {
        "model": {"base": "flux-12b", "d_model": 64, "n_heads": 2,
                  "n_kv_heads": 2, "head_dim": 32, "d_ff": 128,
                  "n_layers": 2, "dtype": dtype},
        "form": "dit_uniform", "text_tokens": 256, "text_width": 64,
        "latent_channels": 64, "mesh": {"data": 1, "model": chips},
        "sp": sp or {"strategy": "full"},
        "sampler": {"num_steps": 3, "guidance_scale": guidance},
        "max_batch": 2,
        "check": {"requests": check_requests, "rel_err_limit": limit}}
    traffic = {"loop": "open", "rate_per_s": 4.0, "lengths": [64, 128],
               "shares": [0.5, 0.5], "drain_s": 30}
    e2e = ["latency_p50_s", "latency_p90_s", "setup_s"]
    return spec.Cell("tiny", chips, config, traffic,
                     {n: {"name": n, "unit": "s"} for n in e2e},
                     {"queue_wait_p90_s.image":
                      {"name": "queue_wait_p90_s.image", "unit": "s"}})


@pytest.fixture
def tiny():
    return tiny_cell
