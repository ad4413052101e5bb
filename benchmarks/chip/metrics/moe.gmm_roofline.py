"""moe.gmm_roofline: least time of the routed experts' matmuls over the
time their grouped-matmul kernels took (moe.expert_ms), in %.

The least time is the running cell's expected routed rows per step
(tokens x top_k x held / n_experts per MoE layer), three d x ff products
each, forward and backward with nothing recomputed
(configs/<config>.py expert_flops_per_step), at the chip's peak bf16
rate: what the held experts must compute, not how the kernels tile it.

A reader is handed no cell (xtrace.Run), so the running cell is found
among the cells that list this metric by the facts the run does carry:
tokens per step, chips and flops_per_token. Where those cells disagree on
the experts' operations, or none matches, the metric is left out."""

import cells

NAME = "moe.gmm_roofline"


def expert_flops(run, bench=None) -> float | None:
    """The routed experts' operations per step of the cell whose facts
    `run` carries, or None where the listed cells do not settle it."""
    bench = bench or cells.Bench()
    names = next(m["workloads"] for m in bench.spec["per_layer"]
                 if m["name"] == NAME)
    found = set()
    for name in names:
        cell = bench.cell(name)
        if (cell.tokens_per_step == run.tokens_per_step
                and cell.chips == run.chips
                and cell.ref.flops_per_token(cell.cfg, cell.traffic["seq"])
                == run.flops_per_token):
            found.add(cell.ref.expert_flops_per_step(cell.cfg,
                                                     run.tokens_per_step))
    return found.pop() if len(found) == 1 else None


def read(run):
    expert = cells.load_module(cells.HERE / "metrics" / "moe.expert_ms.py",
                               "metric_moe.expert_ms")
    ms = expert.read(run)
    if not ms or run.peak is None:
        return None
    flops = expert_flops(run)
    if flops is None:
        return None
    return 100.0 * flops / run.peak["bf16_flops"] / (ms / 1e3)
