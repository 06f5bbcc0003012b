"""_exchange_sideband round-trip property (migration sideband exchange).

The migration path silently relies on the exactly-one-writer-per-slot
invariant: ``_exchange_sideband`` scatters each sequence's side info into
a zero buffer at its destination slot and SUMS the combined buffers, so a
slot bijection must round-trip every key exactly — any double-write or
missed slot corrupts labels/seq_len/similarity history. Property-tested
single-device (pure permutation path) and checked on 8 forced host
devices through both comm modes (subprocess, like test_comm.py).
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, st   # optional dep; skips when absent

from repro.core.moe_layer import _exchange_sideband

ROOT = os.path.join(os.path.dirname(__file__), "..")


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([1, 2, 5, 8, 16]))
def test_single_device_bijection_roundtrips_every_key(seed, n_seq):
    r = np.random.default_rng(seed)
    perm = r.permutation(n_seq).astype(np.int32)
    sb = {
        "labels": jnp.asarray(r.integers(0, 1000, (n_seq, 6)), jnp.int32),
        "seq_len": jnp.asarray(r.integers(1, 7, (n_seq,)), jnp.int32),
        "s": jnp.asarray(r.standard_normal((n_seq, 3, 3)), jnp.float32),
    }
    out = _exchange_sideband(sb, jnp.asarray(perm), n_seq, 1, None)
    assert set(out) == set(sb)
    for k, v in sb.items():
        got = np.asarray(out[k])
        # slot perm[i] now holds what slot i held before — exactly
        np.testing.assert_array_equal(got[perm], np.asarray(v))


def test_multi_device_bijection_roundtrips_every_key():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.comm import CommContext, make_mesh
        from repro.core.moe_layer import _exchange_sideband

        n_seq, S = 4, 6
        for seed, (mode, shape, axes) in enumerate([
                ("flat", (8,), ("model",)),
                ("hier", (2, 4), ("node", "local")),
                ("flat", (8,), ("model",))]):
            M = 8
            mesh = make_mesh(shape, axes)
            ax = axes[0] if len(axes) == 1 else axes
            comm = CommContext.build(mode, ax)
            r = np.random.default_rng(seed)
            perm = r.permutation(M * n_seq).astype(np.int32)
            sb = {
                "labels": r.integers(0, 10_000, (M * n_seq, S)).astype(
                    np.int32),
                "seq_len": r.integers(1, S + 1, (M * n_seq,)).astype(
                    np.int32),
                "s": r.standard_normal((M * n_seq, 3, 3)).astype(
                    np.float32),
            }

            def inner(perm_l, lbl_l, sl_l, s_l):
                out = _exchange_sideband(
                    {"labels": lbl_l, "seq_len": sl_l, "s": s_l},
                    perm_l, n_seq, M, comm)
                return out["labels"], out["seq_len"], out["s"]

            fn = shard_map(
                inner, mesh=mesh,
                in_specs=(P(ax), P(ax, None), P(ax), P(ax, None, None)),
                out_specs=(P(ax, None), P(ax), P(ax, None, None)))
            got = fn(jnp.asarray(perm), jnp.asarray(sb["labels"]),
                     jnp.asarray(sb["seq_len"]), jnp.asarray(sb["s"]))
            for g, (k, v) in zip(got, sb.items()):
                # destination slot perm[i] holds source slot i's value
                assert np.array_equal(np.asarray(g)[perm], v), (mode, k)
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
