"""Smoke test of the system's main paths on one TPU chip.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # four chips: phase (e) only

Phases, all in this one process (a chip belongs to one process):

  (a) device: a TPU must be present (no CPU fallback); the ``"auto"``
      codec must resolve to the single-kernel ``fused-mono`` pair, and the
      compiled compress program must hold a Mosaic kernel
      (``tpu_custom_call``), i.e. the kernels are not interpreted.
  (b) codec at the paper's field size: 100 MB of f32 (one Hurricane ISABEL
      field, 100x500x500) and its u16 quantization codes, generated from
      ``--seed``.  Each ``"auto"`` blob must be byte-identical to the
      pure-XLA ``xla`` backend on the same chip and decode to the input;
      a ``deflate-full`` blob of the codes must round-trip and ``lossy-fz``
      on the field must hold its error bound.
  (c) trainer: ``launch/train.py``'s ``train_loop`` at llama3.2-1b's
      published widths with depth cut to 4 of 16 layers, saving a
      compressed checkpoint through the async writer; ``restore_latest``
      into the train step's shardings must be bit-exact, and the next step
      from the restored state must give the uninterrupted run's loss.
  (d) serving: ``ServingEngine`` with the paged KV tier at a resident
      budget below the working set must emit the dense engine's tokens.
  (e) ``--four-chips``: the compressed cross-pod gradient exchange against
      the uncompressed step on a 2x2 (pod, data) mesh, and ``compress_many``
      sharded over four devices against the single-device dispatch.

Timings printed on the way are smoke timings (one cold run each, compile
included where said), not benchmark metrics.  The last line of standard
output is one JSON object; it is printed only when every check passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
FIELD_BYTES = 100 * 500 * 500 * 4  # one Hurricane ISABEL f32 field


class SmokeFailure(Exception):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def log(msg):
    print(msg, flush=True)


def phase_device(n_chips):
    import jax
    import jax.numpy as jnp

    from repro.core import lzss

    devs = jax.devices()
    d = devs[0]
    log(f"[a] device: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    check(d.platform == "tpu", "JAX found a TPU")
    check(len(devs) >= n_chips, f"at least {n_chips} chip(s) visible")
    backend = lzss.resolve_backend("auto")
    decoder = lzss.resolve_decoder("auto")
    log(f"[a] 'auto' resolves to compressor={backend} decoder={decoder}")
    check(backend == "fused-mono", "'auto' compressor is fused-mono")
    check(decoder == "fused-mono", "'auto' decoder is fused-mono")
    cfg = lzss.resolve_chunk_geometry(lzss.LZSSConfig(symbol_size=4, backend="auto"))
    x = jax.ShapeDtypeStruct((64, cfg.chunk_symbols), jnp.int32)
    text = jax.jit(lambda s: lzss.compress_chunks(s, cfg)).lower(x).compile().as_text()
    check("tpu_custom_call" in text, "compiled compress program has a tpu_custom_call")
    return d


def _codec_case(name, raw, symbol_size, deflate=False, lossy_data=None):
    import numpy as np

    from repro.core import lzss

    auto = lzss.LZSSConfig(symbol_size=symbol_size, backend="auto")
    ref = lzss.LZSSConfig(symbol_size=symbol_size, backend="xla")
    t0 = time.perf_counter()
    res = lzss.compress(raw, auto)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = lzss.compress(raw, auto)
    t_warm = time.perf_counter() - t0
    log(
        f"[b] {name}: {raw.size} bytes S={symbol_size} ratio={res.ratio} "
        f"(smoke timing: auto compress first call {t_first} s incl. compile "
        f"and tuning, second call {t_warm} s)"
    )
    t0 = time.perf_counter()
    res_ref = lzss.compress(raw, ref)
    log(f"[b] {name}: xla reference compress {time.perf_counter() - t0} s")
    check(
        res.total_bytes == res_ref.total_bytes
        and np.array_equal(res.data, res_ref.data),
        f"{name}: fused-mono blob byte-identical to xla ({res.total_bytes} bytes)",
    )
    t0 = time.perf_counter()
    out = lzss.decompress(res.data)
    log(f"[b] {name}: auto decompress {time.perf_counter() - t0} s incl. compile")
    check(np.array_equal(out, raw), f"{name}: default decode returns the input")
    if deflate:
        t0 = time.perf_counter()
        ent = lzss.compress(raw, dataclasses.replace(auto, backend="deflate-full"))
        out = lzss.decompress(ent.data)
        log(f"[b] {name}: deflate-full round trip {time.perf_counter() - t0} s")
        check(
            np.array_equal(out, raw),
            f"{name}: deflate-full round-trips (ratio {ent.ratio})",
        )
    if lossy_data is not None:
        eb = 1e-2
        cfg = lzss.LZSSConfig(symbol_size=4, backend="lossy-fz", lossy_eb=eb)
        t0 = time.perf_counter()
        lres = lzss.compress(lossy_data, cfg)
        back = lzss.decompress(lres.data).view(np.float32)
        log(f"[b] {name}: lossy-fz round trip {time.perf_counter() - t0} s")
        err = float(np.max(np.abs(back - lossy_data)))
        check(
            err <= eb,
            f"{name}: lossy-fz max |x'-x| = {err} <= {eb} (ratio {lres.ratio})",
        )


def phase_codec(seed, field_bytes=FIELD_BYTES):
    import numpy as np

    from repro.data import datasets

    field = datasets.hurr_field(field_bytes, seed=seed)
    _codec_case("hurr-field f32", field, 4, lossy_data=field.view(np.float32))
    codes = datasets.hurr_quant(field_bytes // 2, seed=seed)
    # entropy coding is for the quantization codes (the f32 field's bytes
    # are near-incompressible): one deflate-full round trip, on them
    _codec_case(
        "hurr-quant u16", np.ascontiguousarray(codes).view(np.uint8), 2,
        deflate=True,
    )


def _train_args(ckpt_dir, steps, layers, d_model, d_ff, batch, seq):
    return argparse.Namespace(
        arch="llama3.2-1b",
        reduced=False,
        d_model=d_model,
        d_ff=d_ff,
        layers=layers,
        steps=steps,
        batch=batch,
        seq=seq,
        lr=3e-4,
        microbatches=1,
        ckpt_dir=ckpt_dir,
        ckpt_every=10**9,  # the final save only
        async_ckpt=True,
        heartbeat="",
        log_every=1,
    )


def model_config(layers=4, d_model=2048, d_ff=8192):
    """llama3.2-1b at its published widths, depth cut to ``layers``."""
    from repro.launch import train

    return train.build(_train_args("", 1, layers, d_model, d_ff, 1, 1))[0]


def phase_train(workdir, layers=4, d_model=2048, d_ff=8192, batch=4, seq=256):
    import jax
    import numpy as np

    from repro.checkpoint.manager import CheckpointManager
    from repro.data.pipeline import DataConfig, make_batch_for_step
    from repro.launch import steps as steps_lib, train

    log(
        f"[c] llama3.2-1b at d_model={d_model} d_ff={d_ff}, depth cut to "
        f"{layers} of 16 layers, batch={batch} seq={seq}"
    )
    ckpt = os.path.join(workdir, "ckpt")
    kw = dict(layers=layers, d_model=d_model, d_ff=d_ff, batch=batch, seq=seq)
    t0 = time.perf_counter()
    saved_losses, saved = train.train_loop(_train_args(ckpt, 2, **kw))
    log(
        f"[c] 2 steps + async compressed save: {time.perf_counter() - t0} s "
        "incl. compile"
    )
    # host copy only: the device holds one train state at a time
    saved = jax.device_get(saved)
    t0 = time.perf_counter()
    straight_losses, _ = train.train_loop(_train_args("", 3, **kw))
    log(f"[c] uninterrupted 3 steps: {time.perf_counter() - t0} s")
    # resume as train_loop does, into the 3-step run's schedule and shardings
    cfg, traincfg, mesh, shape = train.build(_train_args(ckpt, 3, **kw))
    log(f"[c] {cfg.param_count()} parameters")
    jfn, st_sh, b_sh = steps_lib.make_train_step(cfg, traincfg, mesh, shape)
    mgr = CheckpointManager(ckpt, compress=True)
    template = steps_lib.abstract_train_state(cfg, traincfg)
    t0 = time.perf_counter()
    restored, step = mgr.restore_latest(template, st_sh)
    log(f"[c] restore_latest: {time.perf_counter() - t0} s incl. compile")
    check(step == 2, "restore_latest finds the step-2 checkpoint")
    a = jax.tree_util.tree_leaves_with_path(saved)
    b = jax.tree_util.tree_leaves(restored)
    check(
        len(a) == len(b)
        and all(
            x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y))
            for (_, x), y in zip(a, b)
        ),
        f"restored train state is bit-exact ({len(a)} leaves)",
    )
    check(
        any(np.any(x != 0) for p, x in a if "opt" in jax.tree_util.keystr(p)),
        "Adam moments are non-zero",
    )
    del saved, a, b
    log(f"[c] checkpoint {mgr.stats(2)}")
    dc = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
        global_batch=shape.global_batch, seed=traincfg.seed,
    )
    batch2 = {
        k: jax.device_put(v, b_sh[k])
        for k, v in make_batch_for_step(dc, step).items()
    }
    _, metrics = jfn(restored, batch2)
    resumed = float(metrics["loss"])
    log(f"[c] resumed step-2 loss {resumed} uninterrupted {straight_losses[2]}")
    check(
        resumed == straight_losses[2]
        and np.array_equal(saved_losses, straight_losses[:2]),
        "resumed step's loss equals the uninterrupted run's",
    )
    return cfg


def phase_serve(cfg, seed, budget=8, block_tokens=8, new_tokens=16):
    import numpy as np

    from repro.configs.base import TrainConfig
    from repro.launch import steps as steps_lib
    from repro.serving.engine import ServingEngine

    params = steps_lib.init_train_state(cfg, TrainConfig(), seed)["params"]
    prompts = np.random.default_rng(seed).integers(0, cfg.vocab_size, (2, 8))
    prompts = prompts.astype(np.int32)
    dense = ServingEngine(cfg, params, max_len=64).generate(
        prompts, max_new_tokens=new_tokens
    )
    eng = ServingEngine(
        cfg,
        params,
        max_len=64,
        kv_compress=True,
        kv_offload=True,
        block_tokens=block_tokens,
        budget_blocks=budget,
    )
    t0 = time.perf_counter()
    paged = eng.generate(prompts, max_new_tokens=new_tokens)
    s = eng.paging_stats()
    log(
        f"[d] paged decode {paged.tokens.shape} in {time.perf_counter() - t0} s "
        f"incl. compile; budget {budget} blocks, working set "
        f"{s['working_set_blocks']}, evictions {eng.kv_store.stats.evictions}, "
        f"restores {eng.kv_store.stats.restores}"
    )
    check(s["working_set_blocks"] > budget, "resident budget below the working set")
    check(eng.kv_store.stats.evictions > 0, "blocks were evicted through the codec")
    check(np.array_equal(paged.tokens, dense.tokens), "paged decode tokens equal dense")


def phase_four_chips(seed):
    import jax
    import numpy as np

    from repro import configs
    from repro.configs.base import CompressionConfig, ShapeConfig, TrainConfig
    from repro.core import lzss
    from repro.data import datasets
    from repro.data.pipeline import DataConfig, make_batch_for_step
    from repro.launch import mesh as mesh_lib, steps as steps_lib

    mesh = mesh_lib.make_host_mesh(data=2, model=1, pod=2)
    # llama3.2-1b's layout cut to 2 layers at d_model 256 / d_ff 1024 /
    # vocab 8192: at full width the compressed step (one kernel instance
    # per gradient leaf, decoded on every pod) takes ~20 min to compile
    # for v5e, past this run's budget; the embedding, q/o and MLP leaves
    # are still over the size that sends a leaf through the codec
    cfg = dataclasses.replace(
        configs.get_config("llama3.2-1b"),
        num_layers=2,
        d_model=256,
        d_ff=1024,
        vocab_size=8192,
        model_axis=1,
    )
    log(f"[e] pod exchange on a 2x2 (pod, data) mesh, {cfg.param_count()} params")
    shape = ShapeConfig("t", 256, 8, "train")
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=256, global_batch=8, seed=seed)
    out = {}
    for name, compressed in (("base", False), ("lz", True)):
        # ratio cap 1: a slab whose container does not fit falls back to its
        # u16 codes, so the exchange is exact w.r.t. the u16 quantization the
        # tolerances below are set for (cap 2 would fall back to int8)
        tc = TrainConfig(
            total_steps=4,
            warmup_steps=1,
            compression=CompressionConfig(
                grad_cross_pod=compressed, grad_ratio_cap=1.0
            ),
        )
        jfn, st_sh, b_sh = steps_lib.make_train_step(
            cfg, tc, mesh, shape, compressed=compressed
        )
        state = jax.device_put(steps_lib.init_train_state(cfg, tc, seed), st_sh)
        batch = {
            k: jax.device_put(v, b_sh[k])
            for k, v in make_batch_for_step(dc, 0).items()
        }
        t0 = time.perf_counter()
        state, m = jfn(state, batch)
        out[name] = (float(m["loss"]), float(m["grad_norm"]))
        log(
            f"[e] {name} step: {out[name]} in {time.perf_counter() - t0} s "
            "incl. compile"
        )
    check(abs(out["base"][0] - out["lz"][0]) < 1e-2, "compressed pod step: same loss")
    check(
        abs(out["base"][1] - out["lz"][1]) / out["base"][1] < 0.02,
        "compressed pod step: grad norm within 2%",
    )
    bufs = [
        datasets.hurr_quant(1 << 20, seed=seed + i).view(np.uint8) for i in range(8)
    ]
    single = lzss.compress_many(bufs, lzss.LZSSConfig(symbol_size=2, backend="auto"))
    flat = jax.make_mesh((4,), ("data",))
    sharded = lzss.compress_many(
        bufs,
        lzss.LZSSConfig(
            symbol_size=2, backend="sharded", decoder="sharded", mesh=flat
        ),
    )
    check(
        np.array_equal(single.total_bytes, sharded.total_bytes)
        and np.array_equal(single.data, sharded.data),
        "compress_many sharded over 4 chips byte-identical to single-device",
    )


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    # the autotuner sweeps on the chip into a cache this run starts empty
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(workdir, "autotune.json")

    from repro.runtime.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    n_chips = 4 if args.four_chips else 1
    t_start = time.perf_counter()
    failed = []

    def attempt(name, fn, *a):
        # a failed phase does not stop the next: one run reports them all
        t0 = time.perf_counter()
        try:
            return fn(*a)
        except Exception as e:
            traceback.print_exc()
            print(f"chip_smoke: phase {name} failed: {e!r}", file=sys.stderr)
            failed.append(name)
            return None
        finally:
            log(f"phase {name}: {time.perf_counter() - t0} s (smoke timing)")

    try:
        try:
            dev = phase_device(n_chips)
        except SmokeFailure as e:
            print(f"chip_smoke: check failed, expected: {e}", file=sys.stderr)
            return 1
        if args.four_chips:
            attempt("four-chips", phase_four_chips, args.seed)
        else:
            attempt("codec", phase_codec, args.seed)
            cfg = attempt("train", phase_train, workdir)
            attempt("serve", phase_serve, cfg or model_config(), args.seed)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    import jax

    log(f"all phases passed in {time.perf_counter() - t_start} s")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(jax.devices()),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
