"""step.mfu: the traced window's tokens/s x forward+backward operations
per token (the configuration's `flops_per_token`: every matmul, the head
and attention's score and value products, nothing recomputed) over
chips x peak bf16 operations/s, in %."""


def read(run):
    t = run.trace
    if run.peak is None or run.steps == 0 or t.window_ns <= 0:
        return None
    tokens_per_s = run.steps * run.tokens_per_step / (t.window_ns / 1e9)
    return 100.0 * tokens_per_s * run.flops_per_token \
        / (run.chips * run.peak["bf16_flops"])
