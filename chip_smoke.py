"""Chip smoke test: the COVAP trainer's main path, once, on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chip   # the data-parallel path on four chips

One chip: the two main-path Pallas kernels (``ef_update`` and
``pack_ef_cast`` with a bf16 wire), compiled for the chip, are checked
against their jnp references on one seeded 25 MiB bucket whose length is not
a whole number of blocks.  The fused causal attention of ``attn_train`` is
checked against the q-chunked scan at gpt2-paper's full attention shape
(batch 8, seq 1024, 12 heads of 64), output and gradients, each against a
float32 run of the scan.  Then gpt2-paper at its published width (12
layers, d_model 768, vocab 50257; random weights from ``--seed``) trains
with COVAP at interval 4, seq 1024, global batch 8, on the synthetic loader:
8 steps on the default path (post-backward all-reduce sync, which runs
``ef_update``) and 4 on the arena path (which runs ``pack_ef_cast``).

Four chips (``--four-chip``, and nothing else): on a ``("data",)`` mesh of 4
chips at global batch 16, 2 steps of ``compressor="none"`` agree with the
same global batch on one chip, 2 steps of COVAP at interval 1 agree with
``none``, and 8 steps of COVAP at interval 4 stay finite with the
replicated parameters bitwise equal on all 4 chips.

Everything runs in this one process: a chip belongs to one process at a
time.  Without a TPU the script exits non-zero before it prints a result.
The last line of standard output is one JSON object naming the device.
Step times printed here are smoke readings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "gpt2-paper"
SEQ = 1024
# one 25 MiB DDP bucket of f32 gradients plus one element: a ragged tail
BUCKET_ELEMS = 25 * 1024 * 1024 // 4 + 1
EF_COEFF = 0.7          # c*r is inexact, so FMA vs 2-op rounding shows
ULP_TOL = 2.0           # f32 ulps of |g| + |c*r| (fused FMA vs 2-op form)
BF16_TOL = 2e-2         # relative, DP step vs one-chip large-batch step


def check(ok, msg: str) -> None:
    """A failed check fails the run (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(msg)


def require_kernel(hlo_text: str, what: str) -> None:
    """A compiled Pallas kernel shows up as a ``tpu_custom_call``; an
    interpreted one would not."""
    check("tpu_custom_call" in hlo_text,
          f"{what}: no tpu_custom_call in the executable")


def _f64(x):
    return np.asarray(x, np.float64)


def max_ulps(got, want, scale) -> float:
    """Largest ``|got - want|`` in f32 ulps of ``scale``."""
    spacing = np.spacing(np.abs(np.asarray(scale, np.float32)))
    return float(np.max(np.abs(_f64(got) - _f64(want)) / spacing))


def check_kernels(seed: int, n: int = BUCKET_ELEMS) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.ef_covap import ef_update
    from repro.kernels.pack_ef_cast import pack_ef_cast

    kg, kr = jax.random.split(jax.random.PRNGKey(seed))
    g = jax.random.normal(kg, (n,), jnp.float32)
    r = jax.random.normal(kr, (n,), jnp.float32)
    c = jnp.float32(EF_COEFF)
    scale = np.abs(_f64(g)) + np.abs(EF_COEFF * _f64(r))
    ref_ef = jax.jit(ref.ef_update_ref, static_argnames="selected")
    ref_pack = jax.jit(ref.pack_ef_cast_ref,
                       static_argnames=("selected", "wire_dtype"))
    for selected in (True, False):
        compiled = ef_update.lower(
            g, r, c, selected=selected, interpret=False
        ).compile()
        require_kernel(compiled.as_text(), "ef_update")
        send, rnew = compiled(g, r, c)
        send_ref, rnew_ref = ref_ef(g, r, c, selected=selected)
        kept, empty = (send, rnew) if selected else (rnew, send)
        want = send_ref if selected else rnew_ref
        err = max_ulps(kept, want, scale)
        check(not np.any(np.asarray(empty)), "ef_update: split not exact")
        check(err <= ULP_TOL, f"ef_update selected={selected}: {err} ulp")
        print(f"[kernel] ef_update selected={selected} n={n}: "
              f"max err {err:.3f} ulp vs ref (tol {ULP_TOL})")

        compiled = pack_ef_cast.lower(
            g, r, c, selected=selected, wire_dtype="bfloat16",
            interpret=False,
        ).compile()
        require_kernel(compiled.as_text(), "pack_ef_cast")
        wire, rnew = compiled(g, r, c)
        wire_ref, rnew_ref = ref_pack(g, r, c, selected=selected,
                                      wire_dtype="bfloat16")
        check(wire.dtype == jnp.bfloat16 and rnew.dtype == jnp.float32,
              f"pack_ef_cast dtypes: {wire.dtype}, {rnew.dtype}")
        # the wire value plus its residual is the compensated gradient t
        t_err = max_ulps(_f64(wire) + _f64(rnew),
                         _f64(wire_ref) + _f64(rnew_ref), scale)
        # a 1-ulp difference in t may flip the bf16 rounding by one step
        w32 = np.asarray(wire_ref, np.float32)
        bf16_ulp = np.spacing(np.abs(w32)) * 2.0**16
        w_err = float(np.max(np.abs(_f64(wire) - _f64(wire_ref))
                             / np.maximum(bf16_ulp, np.finfo(np.float32).tiny)))
        if not selected:
            check(not np.any(np.asarray(wire)), "pack_ef_cast: sent unselected")
        check(t_err <= ULP_TOL, f"pack_ef_cast selected={selected}: {t_err}")
        check(w_err <= 1.0, f"pack_ef_cast wire: {w_err} bf16 ulp")
        print(f"[kernel] pack_ef_cast bf16 selected={selected} n={n}: "
              f"max err {t_err:.3f} ulp (wire+residual), "
              f"{w_err:.3f} bf16 ulp (wire) vs ref")


def check_attention(cfg, seed: int, *, batch: int = 8,
                    seq: int = SEQ) -> dict:
    """``attn_train`` on the fused kernel path against the q-chunked scan,
    both in the configuration's compute dtype, and each against the scan in
    float32: the output and the gradients of ``sum(y * dy)`` w.r.t. the
    input and every projection weight.  Returns the gaps by leaf."""
    import jax
    import jax.numpy as jnp

    from repro.models import attention

    check(attention.takes_flash(cfg, seq, 0),
          f"{cfg.name} at seq {seq} does not take the fused kernel")
    kp, kx, kd = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = attention.attn_init(kp, cfg, jnp.float32)
    x = jax.random.normal(kx, (batch, seq, cfg.d_model), jnp.float32)
    dy = jax.random.normal(kd, (batch, seq, cfg.d_model), jnp.float32)

    def run(c, fused: bool):
        def y_and_grads(params, x):
            y, pull = jax.vjp(
                lambda p, x: attention.attn_train(p, x, c).astype(
                    jnp.float32), params, x)
            return y, pull(dy)

        takes = attention.takes_flash
        attention.takes_flash = lambda *a: fused
        try:
            compiled = jax.jit(y_and_grads).lower(params, x).compile()
        finally:
            attention.takes_flash = takes
        if fused:
            require_kernel(compiled.as_text(), "fused attention")
        y, (gp, gx) = compiled(params, x)
        return {"y": y, "dx": gx, **{f"d{k}": v for k, v in gp.items()}}

    want = run(cfg.with_(compute_dtype="float32"), False)
    gaps = {}
    for name, fused in (("fused", True), ("scan", False)):
        got = run(cfg, fused)
        gaps[name] = {k: rel_l2([got[k]], [want[k]]) for k in want}
    for k in want:
        f, s = gaps["fused"][k], gaps["scan"][k]
        print(f"[attention] {k}: rel L2 gap to f32 scan: fused {f:.3e}, "
              f"scan {s:.3e}")
        check(f <= BF16_TOL, f"fused attention {k}: rel L2 gap {f}")
    return gaps


def make_batches(cfg, seq: int, global_batch: int, n: int, seed: int):
    from repro.data import DataConfig, make_loader

    it = iter(make_loader(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=global_batch,
        seed=seed,
    )))
    return [next(it) for _ in range(n)]


def train(model, optimizer, tc, batches, *, seed: int, mesh=None,
          check_kernel: bool = False) -> dict:
    """``tc.steps`` steps through :class:`Trainer`: every phase executable
    is compiled first (timed), then the last phase cycle is timed with
    ``block_until_ready``, with no compile inside it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.train.trainer import Trainer

    dp_axes = ("data",) if mesh is not None else ()
    tr = Trainer(model, optimizer, tc, mesh=mesh, dp_axes=dp_axes)
    state = tr.init_state(jax.random.PRNGKey(seed))
    if mesh is not None:
        rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        state = {**{k: jax.device_put(state[k], rep)
                    for k in ("params", "opt", "comp")},
                 "step": state["step"]}
        batches = [jax.device_put(b, dp) for b in batches]
    params0 = state["params"]
    t0 = time.perf_counter()
    for phase in range(tr.num_phases):
        compiled = tr._phase_fn(phase).lower(
            state["params"], state["opt"], state["comp"], batches[0],
            jnp.asarray(phase, jnp.int32),
        ).compile()
        if check_kernel:
            require_kernel(compiled.as_text(), f"phase {phase} step")
    compile_s = time.perf_counter() - t0
    warm = tc.steps - tr.num_phases
    state = tr.run(state, iter(batches[:warm]), steps=warm, log=None)
    jax.block_until_ready(state["params"])
    t0 = time.perf_counter()
    state = tr.run(state, iter(batches[warm:tc.steps]), steps=tr.num_phases,
                   log=None)
    jax.block_until_ready(state["params"])
    step_s = (time.perf_counter() - t0) / tr.num_phases
    for phase, fn in tr._steps.items():
        check(fn._cache_size() == 1, f"phase {phase} compiled again")
    losses = [h["total_loss"] for h in tr.history]
    check(len(losses) == tc.steps, losses)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    changed = any(
        bool(jnp.any(a != b)) for a, b in zip(
            jax.tree.leaves(params0), jax.tree.leaves(state["params"])
        )
    )
    check(changed, "parameters did not change")
    return {
        "state": state, "losses": losses,
        "grad_norms": [h["grad_norm"] for h in tr.history],
        "compile_s": compile_s, "step_s": step_s,
        "num_phases": tr.num_phases,
    }


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(name: str, out: dict) -> None:
    print(f"[train] {name}: {out['num_phases']} phase executable(s) compiled "
          f"in {out['compile_s']:.2f} s; losses "
          f"{[round(x, 4) for x in out['losses']]}")
    print(f"[train] {name}: step {out['step_s'] * 1e3:.2f} ms "
          f"(smoke reading, not a benchmark); peak_bytes_in_use "
          f"{peak_bytes()}")


def one_chip(cfg, *, seed: int, seq: int = SEQ, global_batch: int = 8,
             bucket_elems: int = BUCKET_ELEMS) -> int:
    from repro.models import build_model
    from repro.optim import adamw, cosine_warmup
    from repro.train.trainer import TrainConfig

    check_kernels(seed, bucket_elems)
    check_attention(cfg, seed, seq=seq)
    model = build_model(cfg)
    batches = make_batches(cfg, seq, global_batch, 8, seed)
    for name, arena, steps in (("covap I=4 default", False, 8),
                               ("covap I=4 arena", True, 4)):
        tc = TrainConfig(compressor="covap", interval=4, arena=arena,
                         steps=steps, log_every=1)
        out = train(model, adamw(cosine_warmup(1.5e-4, 1, steps)), tc,
                    batches, seed=seed, check_kernel=True)
        report(name, out)
        del out
    return 1


def rel_l2(a: list, b: list) -> float:
    num = sum(float(np.sum((_f64(x) - _f64(y)) ** 2)) for x, y in zip(a, b))
    den = sum(float(np.sum(_f64(y) ** 2)) for y in b)
    return (num / max(den, 1e-300)) ** 0.5


def four_chip(cfg, *, seed: int, seq: int = SEQ, global_batch: int = 16,
              devices=None) -> int:
    import jax
    from jax.sharding import Mesh

    from repro.models import build_model
    from repro.optim import sgd
    from repro.train.trainer import TrainConfig

    devices = list(devices if devices is not None else jax.devices()[:4])
    check(len({d.id for d in devices}) == 4, f"need 4 chips: {devices}")
    mesh = Mesh(np.array(devices), ("data",))
    model = build_model(cfg)
    batches = make_batches(cfg, seq, global_batch, 8, seed)
    params0 = jax.tree.leaves(jax.device_get(
        model.init(jax.random.PRNGKey(seed))
    ))

    def run(name, compressor, interval, steps, m):
        tc = TrainConfig(compressor=compressor, interval=interval,
                         steps=steps, log_every=1)
        # SGD: the parameter update is linear in the synced gradient
        out = train(model, sgd(1e-2, momentum=0.9), tc, batches[:steps],
                    seed=seed, mesh=m)
        report(name, out)
        params = jax.tree.leaves(out.pop("state")["params"])
        out["update"] = [
            _f64(p) - q for p, q in zip(jax.device_get(params), params0)
        ]
        out["params"] = params
        return out

    one = run("none W=1 gb16", "none", 1, 2, None)
    dp = run("none W=4 gb16", "none", 1, 2, mesh)
    for key in ("losses", "grad_norms"):
        rel = float(np.max(np.abs(_f64(dp[key]) - _f64(one[key]))
                           / np.abs(_f64(one[key]))))
        check(rel <= BF16_TOL, f"W=4 vs W=1 {key}: rel {rel}")
        print(f"[dp] none W=4 vs W=1 {key}: max rel diff {rel:.3e} "
              f"(tol {BF16_TOL})")
    rel = rel_l2(dp["update"], one["update"])
    check(rel <= BF16_TOL, f"W=4 vs W=1 update: rel L2 {rel}")
    print(f"[dp] none W=4 vs W=1 parameter update: rel L2 {rel:.3e} "
          f"(tol {BF16_TOL})")
    del one

    cov = run("covap I=1 W=4 gb16", "covap", 1, 2, mesh)
    # ulps of |p0| + |update|, the operands of the parameter's last add
    err = max(
        max_ulps(a, b, np.abs(p0) + np.abs(u)) for a, b, p0, u in zip(
            jax.device_get(cov["params"]), jax.device_get(dp["params"]),
            params0, dp["update"],
        )
    )
    check(err <= ULP_TOL, f"covap I=1 vs none: {err} ulp")
    loss_rel = float(np.max(np.abs(_f64(cov["losses"]) - _f64(dp["losses"]))
                            / np.abs(_f64(dp["losses"]))))
    check(loss_rel <= 1e-6, f"covap I=1 vs none losses: rel {loss_rel}")
    print(f"[dp] covap I=1 vs none, W=4: params max err {err:.3f} ulp, "
          f"losses max rel diff {loss_rel:.3e}")
    del cov, dp

    cov = run("covap I=4 W=4 gb16", "covap", 4, 8, mesh)
    for leaf in cov["params"]:
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        check(len(shards) == 4, f"{len(shards)} shards, expected 4")
        check(all(np.array_equal(shards[0], s) for s in shards[1:]),
              "replicated parameters differ across chips")
    print("[dp] covap I=4 W=4: replicated parameters bitwise equal on all "
          "4 chips")
    return 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run the 4-chip data-parallel phase, and only it")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}; "
              f"nothing was run", file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} cache={cache}")
    cfg = get_config(ARCH)
    if args.four_chip:
        count = four_chip(cfg, seed=args.seed)
    else:
        count = one_chip(cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
