"""An architecture's layout module (``layout/<reference>.py``): the
canonical weights of a seed are the same bit for bit as before the
layout moved out of ``weights.py``; a path may name any period position
of the scanned layers; and a new architecture needs only new files: a
configuration, a layout and a reference, beside an untouched copy of the
benchmark."""
import filecmp
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import weights

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
SMALL = dict(num_layers=2, d_model=64, num_heads=2, head_dim=32,
             vocab_size=512, num_experts=4, expert_d_ff=128)

# sha256 (first 16 hex digits) of each canonical float32 leaf for seed
# 2**33 + 16 at SMALL sizes, recorded from weights.canonical as it was
# before the layout modules
PINNED = {
    "moe-gpt2-l4": {
        "attn_norm.bias": "076a27c79e5ace2a",
        "attn_norm.scale": "02722f124d0f1736",
        "embed": "7134bcd5ebb6dd70",
        "final_norm.bias": "5341e6b2646979a7",
        "final_norm.scale": "2f20cd03c9cd392a",
        "moe_norm.scale": "02722f124d0f1736",
        "router": "c7344412daf66cf5",
        "w_down": "d9939a48bf4931ce",
        "w_gate": "de765a1c01ac26a2",
        "w_up": "de43f7710b4e55ef",
        "wk": "0fd3e52a6a5af653",
        "wo": "db868a5f651ef70f",
        "wq": "4e93ad257a36c306",
        "wv": "877184ffd1fc9f67"},
    "moe-transformerxl-l3": {
        "attn_norm.bias": "076a27c79e5ace2a",
        "attn_norm.scale": "02722f124d0f1736",
        "embed": "7134bcd5ebb6dd70",
        "final_norm.bias": "5341e6b2646979a7",
        "final_norm.scale": "2f20cd03c9cd392a",
        "moe_norm.scale": "02722f124d0f1736",
        "router": "c7344412daf66cf5",
        "unembed": "b026168f75f51761",
        "w_down": "22942a1f6df1f0df",
        "w_gate": "de43f7710b4e55ef",
        "w_up": "d7ebce349d7dba51",
        "wk": "33704c08f3a1a179",
        "wo": "79db662565b0b9a6",
        "wq": "877184ffd1fc9f67",
        "wv": "8f641fe823beef1b"},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_canonical_weights_of_a_seed_are_pinned(name):
    conf = dict(json.loads((HERE / "configs" / f"{name}.json").read_text()),
                **SMALL)
    layout = harness.layout_module(conf)
    canon = jax.jit(lambda k: weights.canonical(layout, conf, k))(
        weights.seed_key(2 ** 33 + 16))
    got = {n: hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]
           for n, a in canon.items()}
    assert got == PINNED[name]


class TwoPositions:
    """A layout whose scanned period has two positions."""
    PROGRAM_PATHS = {"embed": ("embed", "table"),
                     "a.w": ("layers", 0, "a", "w"),
                     "b.w": ("layers", 1, "b", "w"),
                     "b.v": ("layers", 1, "b", "v")}


def test_a_path_may_name_any_period_position():
    canon = {n: jnp.full((2,), i, jnp.float32)
             for i, n in enumerate(sorted(TwoPositions.PROGRAM_PATHS))}
    tree = weights.to_program(TwoPositions, canon)
    assert isinstance(tree["layers"], list) and len(tree["layers"]) == 2
    assert set(tree["layers"][0]) == {"a"}
    assert set(tree["layers"][1]["b"]) == {"w", "v"}
    back = weights.from_program(TwoPositions, tree)
    assert sorted(back) == sorted(canon)
    for n in canon:
        assert np.array_equal(back[n], canon[n])
    # a list whose positions leave a gap, or mix with names, is refused
    with pytest.raises(ValueError, match="positions"):
        weights.to_program(TwoPositions, {"b.w": canon["b.w"]})


# ---------------------------------------------------------------------------
# a new architecture from new files alone
# ---------------------------------------------------------------------------

NEW_CONFIG = {
    "arch": "moe-gpt2",
    "reference": "gqa_shared",
    "source": "moe-gpt2 with grouped-query attention and a shared expert",
    "reduced": ["num_layers", "d_model", "num_heads", "head_dim",
                "vocab_size", "num_experts", "expert_d_ff", "num_kv_heads",
                "num_shared_experts", "gated_experts"],
    "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "vocab_size": 512, "num_experts": 4, "top_k": 2,
    "expert_d_ff": 128, "num_shared_experts": 1, "capacity_factor": 2.0,
    "router_aux_coef": 0.01, "use_rope": False, "tie_embeddings": True,
    "norm": "ln", "act": "gelu", "param_dtype": "float32",
    "compute_dtype": "bfloat16", "gated_experts": True,
}

# layout/gqa_shared.py: keys and values of num_kv_heads heads, and one
# gated shared expert of the routed experts' width per MoE layer
NEW_LAYOUT = '''
import dataclasses
import math

import numpy as np

SIZE_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
             "head_dim", "vocab_size", "num_experts", "top_k",
             "expert_d_ff", "num_shared_experts", "capacity_factor",
             "router_aux_coef", "use_rope", "tie_embeddings", "norm", "act",
             "param_dtype", "compute_dtype")


def program_sizes(base):
    a, m = base.attn, base.moe
    return {"num_layers": base.num_layers, "d_model": base.d_model,
            "num_heads": a.num_heads, "num_kv_heads": a.num_kv_heads,
            "head_dim": a.head_dim, "vocab_size": base.vocab_size,
            "num_experts": m.num_experts, "top_k": m.top_k,
            "expert_d_ff": m.d_ff,
            "num_shared_experts": m.num_shared_experts,
            "capacity_factor": m.capacity_factor,
            "router_aux_coef": m.router_aux_coef, "use_rope": a.use_rope,
            "tie_embeddings": base.tie_embeddings, "norm": base.norm,
            "act": base.act, "param_dtype": base.param_dtype,
            "compute_dtype": base.compute_dtype}


def program_config(base, conf):
    attn = dataclasses.replace(
        base.attn, num_heads=conf["num_heads"],
        num_kv_heads=conf["num_kv_heads"], head_dim=conf["head_dim"],
        use_rope=conf["use_rope"])
    moe = dataclasses.replace(
        base.moe, num_experts=conf["num_experts"], top_k=conf["top_k"],
        d_ff=conf["expert_d_ff"],
        num_shared_experts=conf["num_shared_experts"],
        capacity_factor=conf["capacity_factor"],
        router_aux_coef=conf["router_aux_coef"])
    return dataclasses.replace(
        base, num_layers=conf["num_layers"], d_model=conf["d_model"],
        vocab_size=conf["vocab_size"], attn=attn, moe=moe, gated_mlp=True,
        tie_embeddings=conf["tie_embeddings"], norm=conf["norm"],
        act=conf["act"], param_dtype=conf["param_dtype"],
        compute_dtype=conf["compute_dtype"])


def shapes(conf):
    L, d, V = conf["num_layers"], conf["d_model"], conf["vocab_size"]
    q = conf["num_heads"] * conf["head_dim"]
    kv = conf["num_kv_heads"] * conf["head_dim"]
    E, f = conf["num_experts"], conf["expert_d_ff"]
    fs = f * conf["num_shared_experts"]
    s = 1.0 / math.sqrt(d)
    down = 1.0 / math.sqrt(2 * L)
    return {
        "embed": ((V, d), "normal", 0.02),
        "final_norm.scale": ((d,), "ones", 0.0),
        "final_norm.bias": ((d,), "zeros", 0.0),
        "attn_norm.scale": ((L, d), "ones", 0.0),
        "attn_norm.bias": ((L, d), "zeros", 0.0),
        "wq": ((L, d, q), "normal", s),
        "wk": ((L, d, kv), "normal", s),
        "wv": ((L, d, kv), "normal", s),
        "wo": ((L, q, d), "normal", down / math.sqrt(q)),
        "moe_norm.scale": ((L, d), "ones", 0.0),
        "router": ((L, d, E), "normal", s),
        "w_up": ((L, E, d, f), "normal", s),
        "w_gate": ((L, E, d, f), "normal", s),
        "w_down": ((L, E, f, d), "normal", down / math.sqrt(f)),
        "shared.w_up": ((L, d, fs), "normal", s),
        "shared.w_gate": ((L, d, fs), "normal", s),
        "shared.w_down": ((L, fs, d), "normal", down / math.sqrt(fs)),
    }


_LAYER = ("layers", 0)
PROGRAM_PATHS = {
    "embed": ("embed", "table"),
    "final_norm.scale": ("final_norm", "scale"),
    "final_norm.bias": ("final_norm", "bias"),
    "attn_norm.scale": _LAYER + ("attn_norm", "scale"),
    "attn_norm.bias": _LAYER + ("attn_norm", "bias"),
    "wq": _LAYER + ("attn", "wq"),
    "wk": _LAYER + ("attn", "wk"),
    "wv": _LAYER + ("attn", "wv"),
    "wo": _LAYER + ("attn", "wo"),
    "moe_norm.scale": _LAYER + ("moe", "norm", "scale"),
    "router": _LAYER + ("moe", "router", "w_gate"),
    "w_up": _LAYER + ("moe", "experts", "w_up"),
    "w_gate": _LAYER + ("moe", "experts", "w_gate"),
    "w_down": _LAYER + ("moe", "experts", "w_down"),
    "shared.w_up": _LAYER + ("moe", "shared", "w_up"),
    "shared.w_gate": _LAYER + ("moe", "shared", "w_gate"),
    "shared.w_down": _LAYER + ("moe", "shared", "w_down"),
}


def forward_flops_per_token(struct, conf, lengths):
    L, d, V = conf["num_layers"], conf["d_model"], conf["vocab_size"]
    shp = {n: s for n, (s, _, _) in shapes(conf).items()}
    per_layer = 0.0
    for n, s in shp.items():
        if n in ("wq", "wk", "wv", "wo", "router") or n.startswith("shared."):
            per_layer += 2.0 * np.prod(s[1:])
        elif n in ("w_up", "w_gate", "w_down"):
            per_layer += 2.0 * np.prod(s[2:]) * conf["top_k"]
    lens = np.asarray(lengths, np.float64)
    ctx = float(np.sum(lens * (lens + 1) / 2) / np.sum(lens))
    per_layer += 4.0 * ctx * conf["num_heads"] * conf["head_dim"]
    return L * per_layer + 2.0 * V * d
'''

CHECK = r'''
import json, sys
sys.path.insert(0, "benchmarks/onchip")
import jax
import numpy as np
import flops, harness, traffic, weights
from pathlib import Path

assert harness.ROOT == Path("benchmarks/onchip").resolve(), harness.ROOT
cell = harness.resolve(harness.load_spec(), "tiny-gqa.1chip.zipf")
prog = harness.Program(cell, jax.devices())
lay = prog.layout
canon = weights.canonical(lay, cell.conf, weights.seed_key(2 ** 33 + 5))
tree = weights.to_program(lay, canon)
back = weights.from_program(lay, tree)
out = {
    "layout": str(Path(lay.__file__).relative_to(Path.cwd())),
    "reference": harness.reference_module(cell.conf).__file__,
    "kv_heads": prog.cfg.attn.num_kv_heads,
    "shared": prog.cfg.moe.num_shared_experts,
    "same_tree": jax.tree.structure(tree) == jax.tree.structure(prog.struct),
    "names": sorted(back) == sorted(canon),
    "equal": all(np.array_equal(back[n], canon[n]) for n in canon),
    "flops": flops.train_flops_per_token(lay, prog.struct, cell.conf,
                                         traffic.lengths(cell.traffic)),
    "lengths": traffic.lengths(cell.traffic).tolist(),
}
try:
    weights.check_layout(
        harness.load_module(harness.ROOT / "layout" / "moe_decoder.py"),
        cell.conf, prog.struct)
    out["old_layout"] = "accepted"
except ValueError as e:
    out["old_layout"] = "refused: " + str(e)[:120]
print(json.dumps(out))
'''


def test_a_new_architecture_needs_only_new_files(tmp_path):
    bench = tmp_path / "benchmarks" / "onchip"
    shutil.copytree(HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(bench) for p in bench.rglob("*") if p.is_file()}
    # the three new files, and the entries BENCHMARK.json would gain
    (bench / "configs" / "tiny-gqa.json").write_text(json.dumps(NEW_CONFIG))
    (bench / "layout" / "gqa_shared.py").write_text(NEW_LAYOUT)
    shutil.copy(HERE / "reference" / "moe_decoder.py",
                bench / "reference" / "gqa_shared.py")
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-gqa",
                            "file": "benchmarks/onchip/configs/tiny-gqa.json",
                            "reduced": NEW_CONFIG["reduced"]})
    spec["workloads"].append({"name": "tiny-gqa.1chip.zipf",
                              "config": "tiny-gqa",
                              "traffic": "zipf-s1024-b8", "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(CHECKOUT / "src"))
    r = subprocess.run([sys.executable, "-c", CHECK], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])

    # no file of the copy that was there before was changed
    for rel in before:
        assert filecmp.cmp(bench / rel, HERE / rel, shallow=False), rel
    assert out["layout"] == "benchmarks/onchip/layout/gqa_shared.py"
    assert out["reference"].endswith("reference/gqa_shared.py")
    assert (out["kv_heads"], out["shared"]) == (2, 1)
    assert out["same_tree"] and out["names"] and out["equal"]
    assert out["old_layout"].startswith("refused")

    # by hand: q 4 x 16 = 64 wide, k and v 2 x 16 = 32, one shared expert
    # of three 64 x 128 matrices beside two routed ones of three each
    c = NEW_CONFIG
    lens = np.asarray(out["lengths"], np.float64)
    ctx = np.sum(lens * (lens + 1) / 2) / np.sum(lens)
    d, q, kv, f = 64, 64, 32, 128
    attn = 2 * (d * q + 2 * d * kv + q * d)
    scores = 2 * 2 * ctx * q
    router = 2 * d * c["num_experts"]
    routed = 2 * 3 * d * f * c["top_k"]
    shared = 2 * 3 * d * f
    head = 2 * c["vocab_size"] * d
    hand = 3 * (c["num_layers"] * (attn + scores + router + routed + shared)
                + head)
    assert out["flops"] == pytest.approx(hand, rel=1e-12)
