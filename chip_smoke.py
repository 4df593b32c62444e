"""Chip smoke test: the served path on one TPU at Qwen3-0.6B's published widths.

    python chip_smoke.py [--seed N] [--requests N]

Runs from the repository root, in one process, on a machine with a TPU.
The phases run in order and any failure raises, so the exit code is
non-zero and no verdict is printed:

  a. the default device is a TPU; anything else exits non-zero, naming
     the platform it found (the script never continues on the CPU);
  b. JAX's persistent compile cache is placed (``repro.compile_cache``);
  c. each compiled decode kernel matches its reference on the same
     inputs at Qwen3-0.6B widths, within ``TOL``: the paged kernel
     against the gather path of ``kernels.ops``, the slot kernel against
     ``kernels.ref.decode_attention_ref`` (``force_ref`` of ``ops``);
  d. requests of the paper's workload (``generate_stream`` over Table I's
     tasks) are served on the wall clock through ``LLMServer`` and the
     paged ``ContinuousBatchingEngine`` with the Pallas paged kernel,
     built by ``repro.launch.serve.build_server`` from random weights.
     Every request returns exactly ``budget + max_extra`` tokens, and the
     compiled decode chunk contains the kernel (``tpu_custom_call``);
  e. the allocator's integer budgets, solved in this process, equal
     those of a CPU-only process (``CPU_BUDGETS``).

Lines before the last are information (compile counts and seconds,
donation, peak device memory, and a wall tokens/s that is a smoke figure,
not a metric). The last line is the verdict:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Everything is generated from ``--seed``; nothing is read from disk.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Qwen3-0.6B attention widths, and the serving engine's paging
B, NKV, G, HD = 8, 8, 2, 128
BLOCK, CAPACITY = 16, 1024
# the long-reasoning cell's table: 4240 tokens of 16
LONG_BT = 265
# bf16 inputs, float32 accumulation: both sides round the softmax weights
# to bf16 once, at different points
TOL = dict(rtol=2e-2, atol=2e-2)
MAX_EXTRA = 8
# Table I's integer budgets (AIME, GSM8K, GPQA, CRUXEval, BBH,
# ARC-Challenge) at the paper's operating point, solved on the CPU
CPU_BUDGETS = np.array([0, 341, 0, 0, 346, 30])


def info(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check_platform() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {dev.platform!r}"
                 f" ({dev.device_kind})")
    return dev


def _close(name: str, got, want) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.all(np.isfinite(got)), f"{name}: non-finite output"
    err = float(np.max(np.abs(got - want)))
    info(phase="kernels", kernel=name, shape=list(got.shape), max_abs_err=err)
    np.testing.assert_allclose(got, want, err_msg=name, **TOL)


def paged_case(rng, key, q, name: str, n_bt: int, retired: int = -1,
               past: int = -1) -> None:
    """The compiled paged kernel against the gather path of ``ops`` on
    layer 1 of a shuffled two-layer pool: rows filled to random lengths,
    the unused tail of every table on the sentinel. Row ``retired`` has
    an all-sentinel table (no reference: it must come out zero); row
    ``past`` steps past its 2 blocks, and attends only those."""
    from repro.kernels import ops

    n_pool = B * n_bt
    cap = n_bt * BLOCK
    pos = rng.integers(0, cap, size=B).astype(np.int32)
    pos[0] = 0
    n_blocks = pos // BLOCK + 1
    if past >= 0:
        pos[past], n_blocks[past] = 5 * BLOCK + 3, 2
    if retired >= 0:
        n_blocks[retired] = 0
    tables = rng.permutation(n_pool).reshape(B, n_bt).astype(np.int32)
    tables[np.arange(n_bt)[None, :] >= n_blocks[:, None]] = n_pool
    k_pool, v_pool = (
        jax.random.normal(k, (2, n_pool, NKV, BLOCK, HD), jnp.float32)
        .astype(jnp.bfloat16) for k in jax.random.split(key))
    args = (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(pos))
    got = np.asarray(ops.paged_decode_attention(*args, layer=1), np.float32)
    want = ops.paged_decode_attention(*args, layer=1, force_ref=True)
    live = n_blocks > 0
    _close(name, got[live], np.asarray(want)[live])
    assert not np.any(got[~live]), f"{name}: a retired row is not zero"


def check_kernels(seed: int) -> None:
    """Compiled decode kernels against their references, bf16."""
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)

    q = normal(ks[0], (B, 1, NKV * G, HD))
    paged_case(rng, ks[1], q, "paged_decode_attention", CAPACITY // BLOCK)
    paged_case(rng, ks[2], q, "paged_decode_attention.n_bt265", LONG_BT,
               retired=2, past=3)

    # slot: ragged valid lengths over a 4096-token cache; the reference
    # is ref.decode_attention_ref on the kernel's own layout
    C = 4096
    kc = normal(ks[3], (B, C, NKV, HD))
    vc = normal(ks[4], (B, C, NKV, HD))
    lens = rng.integers(1, C + 1, size=(B, 1))
    valid = jnp.asarray(np.arange(C)[None, :] < lens)
    _close("decode_attention", ops.decode_attention(q, kc, vc, valid),
           ops.decode_attention(q, kc, vc, valid, force_ref=True))


def serve(seed: int, n_requests: int):
    """Wall-clock serving through the engine the server drives."""
    from repro.core import paper_problem
    from repro.launch.serve import SLOTS, build_server
    from repro.queueing_sim import generate_stream
    from repro.serving import ServerConfig

    prob = paper_problem()
    srv = build_server(
        prob, ServerConfig(mode="wall", batch_size=SLOTS,
                           max_extra_tokens=MAX_EXTRA,
                           online_adaptation=False),
        seed=seed)
    stream = generate_stream(prob.tasks, prob.server.lam, n_requests,
                             seed=seed)
    t = time.perf_counter()
    rep = srv.run(stream)
    wall = time.perf_counter() - t
    done = srv.completed
    assert len(done) == n_requests, (len(done), n_requests)
    bad = [(c.rid, c.budget, c.n_tokens) for c in done
           if c.n_tokens != c.budget + MAX_EXTRA]
    assert not bad, f"requests with a wrong token count: {bad}"
    info(phase="serve", requests=len(done),
         budgets=sorted({c.budget for c in done}),
         tokens=rep.tokens_generated, wall_s=wall,
         smoke_tokens_per_s=rep.tokens_generated / wall)
    return srv


def check_decode_hlo(srv) -> None:
    """The compiled decode chunk of the served engine holds the kernel."""
    eng = srv.engine
    n = eng.max_slots
    keys, gidx = eng._keys_gidx()
    lowered = eng._scan.lower(
        eng.params, jnp.zeros(n, jnp.int32), eng.cache,
        jnp.zeros(n, bool), jnp.zeros(n, jnp.int32), keys, gidx,
        chunk=eng.chunk)
    text = lowered.compile().as_text()
    n_calls = text.count("tpu_custom_call")
    info(phase="decode_hlo", tpu_custom_calls=n_calls)
    assert n_calls > 0, "decode chunk compiled without the Pallas kernel"


def check_allocator(srv) -> None:
    """The budgets the server deploys equal the CPU-only solve's.

    ``CPU_BUDGETS`` is what ``solve(paper_problem())`` gives in a process
    with no accelerator (a CPU test pins it); here the same solve runs in
    the process that holds the TPU.
    """
    deployed = np.asarray(srv.allocator.solution.lengths_int)
    info(phase="allocator", budgets=deployed.tolist())
    assert np.array_equal(deployed, CPU_BUDGETS), (deployed, CPU_BUDGETS)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=16)
    args = ap.parse_args()

    dev = check_platform()                                           # a
    from repro import compat, compile_cache
    from repro.obs import jax_hooks

    info(phase="cache", dir=compile_cache.enable())                  # b
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_s.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    donation = compat.donation_supported()
    info(phase="donation", supported=donation)
    assert donation, "buffer donation is off: every chunk would copy the KV"

    check_kernels(args.seed)                                         # c
    srv = serve(args.seed, args.requests)                            # d
    check_decode_hlo(srv)
    check_allocator(srv)                                             # e

    info(phase="compiles", traces=jax_hooks.trace_counts(),
         backend_compile_s=sum(compile_s), n_backend_compiles=len(compile_s),
         peak_bytes_in_use=dev.memory_stats().get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
