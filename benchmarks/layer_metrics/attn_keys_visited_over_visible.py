"""Key columns the windowed attention kernel walks over key columns its
band lets through, summed over a step's queries and the layers whose
window binds: the program's own counter, from shapes alone
(`window_key_columns` of the denoiser: ops/flash_attention.py's blocks).
1 = every masked block skipped and nothing visited is masked; None where
the program has no such counter or no window binds. Layer: Kernels."""


def compute(spans, trace, counters):
    visited, visible = counters.get("attn_key_columns", (0, 0))
    return visited / visible if visible else None
