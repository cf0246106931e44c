"""On-chip smoke check: Qwen2-1.5B at its published widths through Cluster.serve.

Run from the root of a checkout on a TPU host, as one process:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-device path, on four chips

With no option it runs, in order:

  1. device: JAX must report a TPU; there is no CPU fallback,
  2. compile cache: the repo's persistent cache, entries counted at start/end,
  3. model: Qwen2-1.5B (28 layers, vocab 151936, bf16, Pallas on auto) with
     random weights from ``--seed``,
  4. a mixed fleet serving 16 requests (prompts 32-256 tokens, 32 new each),
  5. a prefill/decode-disaggregated fleet serving the same requests, and the
     Pallas prefill program checked for its kernel and against the jnp path.

``--chips 4`` runs only the wallclock backend's multi-device path beside the
same job on one device, and reports where serve engines' caches land.

A phase that fails raises, so the script exits non-zero.  The last line of
stdout is one JSON object naming the device: ``{"ok": true, "device": ...}``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import statistics
import sys
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cluster import Cluster, ServeJob, SimJob  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.wallclock import WallclockBackend  # noqa: E402
from repro.kernels.autotune import enable_compilation_cache  # noqa: E402
from repro.kernels.prefill.ops import length_bucket  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.obs import Tracer  # noqa: E402
from repro.serve.engine import DecodeEngine, Request  # noqa: E402

ARCH = "qwen2-1.5b"
N_REQUESTS = 16
PROMPT_LEN = (32, 256)
MAX_NEW = 32
MAX_SEQ = 512
MIXED_FLEET = "a=2x8,b=1x4"
DISAGG_FLEET = "p=2.0^prefill,d=1.0x8^decode"
FOUR_CHIP_FLEET = "4:3:2:1"
# Prefill kernel vs jnp path on the same bf16 weights: max |logit diff| may be
# at most this share of the reference's max |logit|.  Both run 28 bf16 layers;
# they round differently (the kernel keeps scores and probabilities in f32).
# 1/16 is eight bf16 ulps (2^-7 each) of the top logit; a wrong mask, scale or
# head mapping moves logits by the order of the logits themselves.
LOGITS_RTOL = 1.0 / 16


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def check_device() -> tuple[str, int]:
    """Phase 1: exit non-zero unless JAX's first device is a TPU."""
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX reports platform {d.platform!r}")
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    return d.device_kind, len(devices)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def build_model(cfg, seed: int) -> tuple[Model, dict]:
    """Phase 3: the model with random weights from ``seed``, on the device."""
    model = Model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.key(seed)))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_q_heads}/{cfg.n_kv_heads} vocab={cfg.vocab_size} "
        f"dtype={cfg.param_dtype} use_pallas={cfg.use_pallas} "
        f"param_bytes={nbytes} init_s={time.perf_counter() - t0:.3f} "
        f"peak_bytes_in_use={peak_bytes()}")
    return model, params


def make_requests(seed: int, n: int, prompt_len: tuple[int, int],
                  max_new: int, vocab: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_len[0], prompt_len[1] + 1, n)
    return [
        Request(rid=i, prompt=rng.integers(0, vocab, int(n_tok)).tolist(),
                max_new_tokens=max_new)
        for i, n_tok in enumerate(lens)
    ]


def serve(fleet, model: Model, params, requests: list[Request],
          max_seq: int) -> tuple[object, dict[str, DecodeEngine], Tracer]:
    """Phases 4 and 5: serve ``requests`` on ``fleet`` through a traced
    ``Cluster.serve``; every request must finish exactly once with all of
    its tokens.  Step and prefill times are the engines' ``engine.step``
    and ``engine.prefill`` spans."""
    engines: dict[str, DecodeEngine] = {}

    def factory(spec):
        eng = DecodeEngine(model, params, max_batch=spec.concurrency,
                           max_seq=max_seq, name=spec.name)
        engines[spec.name] = eng
        return eng

    tracer = Tracer()
    t0 = time.perf_counter()
    rep = Cluster(fleet, backend="wallclock", trace=tracer).serve(
        ServeJob(requests, engine_factory=factory, max_seq=max_seq))
    wall = time.perf_counter() - t0
    done = collections.Counter(
        e.data["rid"] for e in tracer.events if e.kind == "request_done")
    bad = [r.rid for r in requests
           if done[r.rid] != 1 or not r.done
           or len(r.out_tokens) != r.max_new_tokens]
    require(not bad, f"{fleet}: requests not served exactly once with all "
                     f"their tokens: {bad}")
    log(f"serve {fleet}: {len(requests)}/{len(requests)} requests served "
        f"exactly once, tokens={int(rep.work_done)} wall_s={wall:.3f} "
        f"backend={rep.backend} mode={rep.metrics.get('mode', 'waves')}")
    for name, e in engines.items():
        step_s = [s.seconds for s in tracer.spans
                  if s.name == "engine.step" and s.worker == name
                  and s.attrs["active"]]
        if step_s:
            steady = step_s[1:]
            log(f"  engine {name}: slots={e.max_batch} steps={len(step_s)} "
                f"first_step_s={step_s[0]:.3f} steady_step_s="
                + (f"{statistics.median(steady):.6f} (median of "
                   f"{len(steady)})" if steady else "none"))
        by_bucket = collections.defaultdict(list)
        for s in tracer.spans:
            if s.name == "engine.prefill" and s.worker == name:
                by_bucket[s.attrs["bucket"]].append(s.seconds)
        for bucket, ts in sorted(by_bucket.items()):
            log(f"  engine {name}: prefill bucket={bucket} calls={len(ts)} "
                f"first_s={ts[0]:.3f} steady_s="
                + (f"{statistics.median(ts[1:]):.6f}" if ts[1:] else "none"))
    return rep, engines, tracer


def prefill_program(model: Model, params, prompt: list[int], max_seq: int):
    """The engine's bucketed prefill (``DecodeEngine.prefill``) for
    ``prompt``: the jitted function and its arguments."""
    bucket = length_bucket(len(prompt), max_seq)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(prompt)] = prompt

    def run(params, toks, last_pos):
        return model.prefill(params, {"tokens": toks}, last_pos=last_pos)

    return jax.jit(run), (params, jnp.asarray(toks), jnp.int32(len(prompt) - 1))


def check_prefill_logits(model: Model, params, prompt: list[int],
                         max_seq: int) -> float:
    """Phase 5: last-token logits of the prefill program against the same
    weights run with ``use_pallas=False``.  Returns the max abs difference."""
    fn, args = prefill_program(model, params, prompt, max_seq)
    ref_model = Model(dataclasses.replace(model.cfg, use_pallas=False))
    ref_fn, _ = prefill_program(ref_model, params, prompt, max_seq)
    v = model.cfg.vocab_size
    got = np.asarray(fn(*args)[0][0, 0, :v], np.float32)
    ref = np.asarray(ref_fn(*args)[0][0, 0, :v], np.float32)
    require(np.all(np.isfinite(got)) and np.all(np.isfinite(ref)),
            "prefill logits are not finite")
    diff = float(np.max(np.abs(got - ref)))
    bound = LOGITS_RTOL * float(np.max(np.abs(ref)))
    log(f"prefill logits: bucket={args[1].shape[1]} max_abs_diff={diff:.6g} "
        f"bound={bound:.6g} (1/16 of max |ref logit|) "
        f"argmax_equal={int(got.argmax()) == int(ref.argmax())}")
    require(diff <= bound, f"prefill kernel logits differ from the jnp path "
                           f"by {diff:.6g} > {bound:.6g}")
    return diff


def four_chips(cfg, seed: int) -> None:
    """The multi-device path: the wallclock backend spreads workers over
    every device; the same job on device 0 alone is the comparison."""
    devices = jax.devices()
    require(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX reports "
                               f"{len(devices)}")
    job = SimJob(size=96, n_jobs=2)
    for label, devs in (("all devices", devices), ("device 0", devices[:1])):
        backend = WallclockBackend(devices=list(devs), seed=seed)
        rep = Cluster(FOUR_CHIP_FLEET, priors="spec",
                      backend=backend).simulate(job)
        stats = backend.stats()
        log(f"simulate {FOUR_CHIP_FLEET} on {label}: "
            f"predicted_speedup={rep.predicted_speedup:.4f} "
            f"measured_speedup={rep.measured_speedup:.4f} "
            f"wall_s={stats.wall_s:.4f} device_of={stats.device_of} "
            f"devices={[str(devs[i]) for i in sorted(set(stats.device_of.values()))]}")
        if len(devs) >= 4:
            require(len(set(stats.device_of.values())) == 4,
                    f"expected four distinct devices, got {stats.device_of}")
    model, params = build_model(cfg, seed)
    reqs = make_requests(seed, 4, (8, 16), 4, cfg.vocab_size)
    _, engines, _ = serve(MIXED_FLEET, model, params, reqs, max_seq=64)
    for name, e in engines.items():
        where = {str(d) for x in jax.tree.leaves(e.caches) for d in x.devices()}
        log(f"  engine {name}: cache on {sorted(where)}")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip (default); "
                         "4: only the multi-device wallclock path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    kind, count = check_device()
    if args.chips == 4:
        four_chips(get_config(ARCH), args.seed)
    else:
        cache_dir = enable_compilation_cache()
        log(f"compile cache: {cache_dir} entries_at_start="
            f"{cache_entries(cache_dir)}")
        cfg = get_config(ARCH)
        model, params = build_model(cfg, args.seed)

        def requests():
            return make_requests(args.seed, N_REQUESTS, PROMPT_LEN, MAX_NEW,
                                 cfg.vocab_size)

        serve(MIXED_FLEET, model, params, requests(), MAX_SEQ)
        reqs = requests()
        serve(DISAGG_FLEET, model, params, reqs, MAX_SEQ)
        prompt = max((r.prompt for r in reqs), key=len)
        fn, fargs = prefill_program(model, params, prompt, MAX_SEQ)
        hlo = fn.lower(*fargs).compile().as_text()
        require("tpu_custom_call" in hlo,
                "the prefill program holds no Pallas kernel (tpu_custom_call)")
        log(f"prefill program: bucket={fargs[1].shape[1]} has tpu_custom_call")
        check_prefill_logits(model, params, prompt, MAX_SEQ)
        log(f"peak_bytes_in_use={peak_bytes()}")
        log(f"compile cache: {cache_dir} entries_at_end="
            f"{cache_entries(cache_dir)}")
    print(json.dumps({"ok": True, "device": {
        "platform": "tpu", "kind": kind, "count": count}}), flush=True)


if __name__ == "__main__":
    main()
