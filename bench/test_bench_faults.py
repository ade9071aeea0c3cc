"""The check fails what it must: a tiny cell's whole run on the CPU, the
chip check skipped, with the timed path broken underneath or the
configuration's control in the program's place, comes out not correct.

Faults that a served cell can have: a step that returns its state
unchanged (decode's cache write dropped), half of the batch left out (odd
slots masked in every segment), a token altered where it is produced, and,
in the int8 block-sparse format, each block's scale read from another.
The exchange between chips has no fault here: every cell runs on one chip.
"""
from bench import run, tiny_cell
from bench import serve_loop as sl

SEED = 2**31 + 99


def _run(tmp_path, fmt="dense", control=False):
    cell = tiny_cell.write(tmp_path, fmt, "closed")
    return run.run_cell(cell, SEED, 2.0, False, control=control,
                        peaks=tiny_cell.PEAKS)


def _wrap_segments(monkeypatch, edit):
    """Apply ``edit(args, out) -> out`` to every decode segment launch."""
    build = sl.build

    def broken(*a, **kw):
        eng, sched = build(*a, **kw)
        for name in ("_slot_segment_paged", "_slot_segment_while_paged"):
            fn = getattr(eng, name)
            setattr(eng, name, lambda *args, _fn=fn: edit(args, _fn))
        return eng, sched

    monkeypatch.setattr(sl, "build", broken)


def test_state_left_unchanged_fails(tmp_path, monkeypatch):
    from repro.models import layers

    monkeypatch.setattr(layers, "paged_cache_write",
                        lambda pool, table, new, pos: pool)
    out = _run(tmp_path)
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > out["checks"]["max_gap"]["limit"]


def test_half_the_batch_left_out_fails(tmp_path, monkeypatch):
    import jax.numpy as jnp

    def odd_masked(args, fn):
        args = list(args)
        active = args[7]
        args[7] = active & (jnp.arange(active.shape[0]) % 2 == 0)
        return fn(*args)

    _wrap_segments(monkeypatch, odd_masked)
    out = _run(tmp_path)
    assert not out["correct"]
    assert out["checks"]["stalled_requests"]["value"] > 0


def test_token_altered_where_produced_fails(tmp_path, monkeypatch):
    import jax.numpy as jnp

    def shifted(args, fn):
        toks, *rest = fn(*args)
        return (jnp.where(toks >= 0, (toks + 1) % 256, toks), *rest)

    _wrap_segments(monkeypatch, shifted)
    out = _run(tmp_path)
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > out["checks"]["max_gap"]["limit"]


def test_block_scale_from_another_block_fails(tmp_path, monkeypatch):
    """Every block of the int8 weights has its own scale, so a projection
    that reads its neighbour's scales serves other numbers."""
    import jax.numpy as jnp

    from repro.core import sonic_layers

    apply = sonic_layers.serve_quant_apply

    def rolled(p, x):
        return apply({**p, "qscales": jnp.roll(p["qscales"], 1, axis=0)}, x)

    monkeypatch.setattr(sonic_layers, "serve_quant_apply", rolled)
    out = _run(tmp_path, "int8_block_sparse")
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > out["checks"]["max_gap"]["limit"]


def test_control_is_not_correct(tmp_path):
    out = _run(tmp_path, "int8_block_sparse", control=True)
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > out["checks"]["max_gap"]["limit"]


def test_compile_inside_the_window_fails(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    def compiles(args, fn):
        jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()  # a new program
        return fn(*args)

    _wrap_segments(monkeypatch, compiles)
    out = _run(tmp_path)
    assert not out["correct"]
    assert out["checks"]["compiles_in_window"]["value"] > 0
