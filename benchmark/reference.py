"""The comparison that decides ``correct``, shared by every architecture.

What knows a model is a file of its own, ``architectures/<name>.py``, named
by the configuration's ``architecture`` key (``llama`` without one): the
plain float32 forward ``logits`` and the ``drive`` of the serving forward
(README, "An architecture").  What is here is no architecture's to choose:
the two seeded sequences and how their pages interleave, the chunks and the
decode steps, the error, the tolerance, the control and the verdict.  The
layout of the test is ``chip_smoke.py`` phase b's.
"""

from __future__ import annotations

# Largest |logit - reference| allowed, in units of the reference row's
# standard deviation.  The serving path rounds activations and the cache to
# bfloat16 in every layer: at Qwen3-1.7B's 28 layers that alone measured 0.114
# to 0.150 on a v5e, under XLA attention and under the Pallas kernels alike,
# while one wrong page of a sequence's 44 (the control below) measured 1.39 to
# 1.41 (PR 24's chip runs; chip_smoke.py has 0.095-0.119 and 1.2 at 16 layers).
# 0.30 is twice the first and under a quarter of the second.
LOGIT_TOLERANCE = 0.30


def check_engine(engine, cell, seed: int, rehearsal: bool) -> dict:
    """Serving-path logits against the architecture's ``logits`` for two
    seeded sequences whose pages interleave in one shuffled pool: each is
    prefilled in two chunks (the second behind a live prefix), then both
    decode together beside six padded rows for four steps.  The control
    decodes one step through a table with one wrong page and must miss the
    tolerance.  A drive may add controls of its own, ``controls(state) ->
    {name: broken state}`` (a state of the wrong sequence, say): one step
    through the right tables from each must miss it too."""
    import numpy as np

    arch = cell.architecture
    runner = engine.runner
    hf, params = cell.hf_config, runner.params
    lens, splits, T = ((88, 40), (48, 16), 64) if rehearsal else ((700, 330), (380, 130), 512)
    n_dec, ps, mp, B = 4, runner.spec.page_size, 64, 8
    rng = np.random.default_rng(seed)
    toks = [rng.integers(2, hf["vocab_size"], size=n + n_dec).astype(np.int32) for n in lens]
    ref = [arch.logits(params, hf, t, list(range(n - 1, n + n_dec)))
           for t, n in zip(toks, lens)]  # row j: logits after token n-1+j

    P = 2 * mp + 1  # page 0 is the garbage page
    perm = rng.permutation(np.arange(1, P)).astype(np.int32)
    tables = [perm[0::2], perm[1::2]]

    def err(logits, want) -> float:
        diff = np.max(np.abs(np.asarray(logits, np.float32) - want))
        return float(diff / np.std(want))

    def prefill_both(drive):
        state = drive.empty(P)
        errs = {}
        for s, (n, split) in enumerate(zip(lens, splits)):
            for lo, hi in ((0, split), (split, n)):
                chunk = np.zeros(T, np.int32)
                chunk[: hi - lo] = toks[s][lo:hi]
                logits, state = drive.prefill(state, s, chunk, lo, hi - lo, tables[s])
            errs[f"prefill[{s}]"] = err(logits, ref[s][0])
        return state, errs

    def decode_both(drive, state, tabs, steps):
        """Rows 0 and 1 are the two sequences, the rest padding."""
        page_tables = np.zeros((B, mp), np.int32)
        entry = np.full(B, mp * ps, np.int32)  # padded rows sit past the table
        for s in range(2):
            page_tables[s], entry[s] = tabs[s], lens[s]
        errs = {}
        for j in range(steps):
            cur = np.zeros(B, np.int32)
            for s in range(2):
                cur[s] = toks[s][lens[s] + j]
            logits, state = drive.decode(state, cur, entry + j, entry, j, page_tables)
            for s in range(2):
                errs[f"decode[{s}]+{j}"] = err(logits[s], ref[s][1 + j])
        return errs

    wrong = [tables[0].copy(), tables[1]]
    wrong[0][1] = tables[1][1]
    errors, control = {}, {}
    for impl in arch.impls(runner, rehearsal):
        drive = arch.drive(runner, impl, B, n_dec)
        prefilled, errors[impl] = prefill_both(drive)
        errors[impl].update(decode_both(drive, prefilled, tables, n_dec))
        control[impl] = decode_both(drive, prefilled, wrong, 1)["decode[0]+0"]
        more = drive.controls(prefilled) if hasattr(drive, "controls") else {}
        for name, broken in more.items():
            control[f"{impl}.{name}"] = decode_both(drive, broken, tables, 1)["decode[0]+0"]
        del prefilled, drive
    worst = max(e for per in errors.values() for e in per.values())
    ok = (all(np.isfinite(e) and e <= LOGIT_TOLERANCE
              for per in errors.values() for e in per.values())
          and all(e > LOGIT_TOLERANCE for e in control.values()))
    return {"ok": bool(ok), "tolerance": LOGIT_TOLERANCE, "worst": worst,
            "errors": errors, "control_errors": control}
