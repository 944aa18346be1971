"""Operations and bytes from SHAPES — never from a kernel's grid or
tiles, so that a share of a peak reads the same work whatever
implements it. That rule binds every reference's arithmetic
(`references/<name>.py`: `serve_flops`, `weight_bytes`,
`kv_bytes_attended`, `train_step_flops`, `flash_attn_flops`); what of
it knows no model is here, written once.

The serving arithmetic is given `work`: what a driver saw between two
step boundaries (`drivers/_serving.py: work_between`), not a model's
reduction of it — `segments` [(start, n)], one a request: `n`
consecutive positions went through the model, the first of them
attending `start + 1` positions; `processed` their sum; `iterations`
the model's forward passes; and every counter of the program as a
delta. A reference reduces the segments as its layers attend.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def context_sum(start, n):
    """Σ of the context lengths attended by `n` consecutive tokens of
    one sequence, the first of which attends `start + 1` positions."""
    return n * start + n * (n + 1) // 2
