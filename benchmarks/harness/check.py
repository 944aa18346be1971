"""The comparison that decides `correct`. Every number compared is
printed beside its limit; limits are data (`benchmarks/limits/
<workload>.json`), set from readings on the chip (PERF.md gives them).

Serving: the widest gap by which a served token's logit lies below the
reference's best, over a seeded sample of the requests the window
finished (the longest among them), the reference run once over each
prompt with its served tokens. Training: the first three steps' losses,
the norm of the first gradient (from the optimizer's first moment) and
the norm of the parameters' change after three steps, each by the worst
leaf as a gap between norms.

The weights and the plain forward pass are the reference's
(`references/<name>.py`, the module `run.py` loaded by the
configuration's `reference` key and hands in as `reference`).
"""
import numpy as np


def _round_up(n, m):
    return -(-int(n) // m) * m


NOISE_SD = 0.03     # the yardstick noise of `served_noise_power`


def expected_gap(margin, sd=NOISE_SD):
    """The gap a position of reference margin `margin` would read, on
    average, if Gaussian noise of standard deviation `sd` lay between
    its best and second-best logit: sd·(φ(u) − u·(1 − Φ(u))), u =
    margin / sd."""
    from math import erf, pi, sqrt

    u = np.asarray(margin, np.float64) / sd
    phi = np.exp(-0.5 * u * u) / sqrt(2 * pi)
    cdf = 0.5 * (1.0 + np.vectorize(erf)(u / sqrt(2)))
    return sd * (phi - u * (1.0 - cdf))


def served_numbers(reference, cfg, seed, sample, rows_to, quant=None):
    """Over every served token compared, the gap by which its logit
    lies below the reference's best (most are 0: the served token IS
    the reference's best): "served_gap_max" the widest, "served_gap_
    mean" the mean, and "served_noise_power": the sum of the gaps over
    the sum they would have, at these positions' own reference margins,
    under the yardstick noise (`expected_gap`). A seed whose model
    decides by wide margins reads small gaps whatever the precision;
    the power divides that out. Each sequence is padded to the next 256
    positions and its served rows to `rows_to` (the mix's longest
    output), so that every seed's sample compiles the same few
    shapes."""
    w = reference.make_weights(cfg, seed, cfg["serve"]["weight_dtype"])
    longest = int(reference.positions(cfg))
    gaps, margins = [], []
    for toks, plen in sample:
        g, m = reference.served_token_gaps(
            cfg, w, toks, plen, min(_round_up(len(toks), 256), longest),
            _round_up(max(rows_to, len(toks) - plen), 128), quant=quant)
        gaps.append(g)
        margins.append(m)
    if not gaps:
        return {"served_gap_mean": None, "served_gap_max": None,
                "served_noise_power": None, "tokens_compared": 0}
    flat, margin = np.concatenate(gaps), np.concatenate(margins)
    yard = float(expected_gap(margin).sum())
    return {"served_noise_power": float(flat.sum()) / yard if yard
            else None,
            "served_gap_mean": float(flat.mean()),
            "served_gap_max": float(flat.max()),
            "tokens_compared": int(flat.size),
            "tokens_off_argmax": int((flat > 0).sum()),
            "margin_p10": float(np.quantile(margin, 0.1)),
            "margin_p50": float(np.median(margin))}


def tree_of(named, tree_position, like):
    """{program name: value} → the reference's leaf layout ({key: a
    scalar, or one value a layer}), `like` giving the shapes."""
    out = {k: np.zeros_like(np.asarray(v, np.float64))
           for k, v in like.items()}
    seen = 0
    for name, val in named.items():
        key, layer = tree_position(name)
        if layer is None:
            out[key] = np.float64(val)
        else:
            out[key][layer] = val
        seen += np.asarray(val).size
    want = sum(np.asarray(v).size for v in like.values())
    if seen != want:
        raise ValueError(f"the program has {seen} leaves, the reference "
                         f"{want}")
    return out


def _flat(tree):
    keys = sorted(tree)
    return np.concatenate([np.asarray(tree[k], np.float64).reshape(-1)
                           for k in keys])


def leaf_labels(tree):
    """Names in `_flat`'s order: key, then [layer(, third)]."""
    out = []
    for k in sorted(tree):
        a = np.asarray(tree[k])
        out += [k + "".join(f"[{i}]" for i in idx)
                for idx in np.ndindex(*a.shape)] if a.ndim else [k]
    return out


def trained_numbers(ref, prog):
    """ref / prog: {"losses", "grad1", "change"} with leaf-norm trees of
    one layout. Gaps are between norms, against the reference's norm of
    that leaf or of the median leaf, whichever is larger. Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    under Adam by round-off alone and are left out of the change."""
    rl, pl = np.asarray(ref["losses"]), np.asarray(prog["losses"])
    g_r, g_p = _flat(ref["grad1"]), _flat(prog["grad1"])
    c_r, c_p = _flat(ref["change"]), _flat(prog["change"])
    g_med, c_med = np.median(g_r), np.median(c_r)
    g_gap = np.abs(g_p - g_r) / np.maximum(g_r, g_med)
    live = g_r >= 1e-3 * g_med
    c_gap = np.abs(c_p - c_r)[live] / np.maximum(c_r[live], c_med)
    out = {f"loss{i + 1}_gap": float(abs(pl[i] - rl[i]) / abs(rl[i]))
           for i in range(len(rl))}
    names = leaf_labels(ref["grad1"])
    live_names = [n for n, keep in zip(names, live) if keep]
    out["_worst"] = {"grad1": names[int(g_gap.argmax())],
                     "change": live_names[int(c_gap.argmax())],
                     "left_out": [n for n, keep in zip(names, live)
                                  if not keep][:6]}
    out.update(grad1_gap_max=float(g_gap.max()),
               grad1_gap_p50=float(np.median(g_gap)),
               change_gap_max=float(c_gap.max()),
               change_gap_p50=float(np.median(c_gap)),
               leaves_left_out=int((~live).sum()))
    return out


def reference_training(reference, cfg, seed, batches, quant=None,
                       keep_rows=None):
    w0 = reference.make_weights(cfg, seed, "float32")
    return reference.train_three_steps(cfg, w0, batches, quant=quant,
                                       keep_rows=keep_rows)


def program_training(reference, check, tree_position, like):
    """The program's readings in the reference's layout: the first
    gradient is moment1 / (1 - beta1) after one step."""
    beta1 = reference.ADAMW["beta1"]
    grad1 = {n: v / (1.0 - beta1) for n, v in check["moment1"].items()}
    return {"losses": check["losses"],
            "grad1": tree_of(grad1, tree_position, like["grad1"]),
            "change": tree_of(check["change"], tree_position,
                              like["change"])}


def judge(numbers, limits):
    """[(name, value, limit, ok)] for every limited number; a number
    that is missing, or not finite, fails."""
    rows = []
    for name, limit in sorted(limits.items()):
        val = numbers.get(name)
        ok = val is not None and np.isfinite(val) and val <= limit
        rows.append((name, val, limit, bool(ok)))
    return rows
