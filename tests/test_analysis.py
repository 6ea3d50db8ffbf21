"""ContractGuard: linter fixtures (good/bad pair per rule + waivers), the
HotLoopRegistry completeness contract, and the layer-2 jaxpr audits over
live servers (1-device and tp=2,ep=4 under device forcing).

Fixture snippets run through the exact production pipeline via
`run_lint(files=...)` — same parsing, same rules, same waiver handling
the CLI uses on the real tree.
"""
import ast
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.analysis.lint import run_lint
from repro.analysis.rules.hygiene import REQUIRED_IGNORES
from repro.configs import reduced_config

REPO = Path(__file__).resolve().parents[1]
GITIGNORE = "".join(p + "\n" for p in REQUIRED_IGNORES)


def rules_hit(files, **kw):
    rep = run_lint(files=files, tracked_files=kw.pop("tracked_files", []),
                   gitignore_text=kw.pop("gitignore_text", GITIGNORE), **kw)
    return rep, {d.rule for d in rep.diagnostics if not d.waived}


# ---------------------------------------------------------------------------
# rule fixtures: one good/bad pair per rule
# ---------------------------------------------------------------------------

def test_donate_jit_choke_point_pair():
    bad = {"src/repro/serving/decode.py":
           "import jax\n"
           "class D:\n"
           "    def __post_init__(self):\n"
           "        self._step = jax.jit(self._step_impl)\n"}
    rep, hit = rules_hit(bad)
    assert "donate-jit-choke-point" in hit
    (d,) = [x for x in rep.diagnostics if x.rule == "donate-jit-choke-point"]
    assert (d.path, d.line) == ("src/repro/serving/decode.py", 4)

    good = {"src/repro/serving/decode.py":
            "class D:\n"
            "    def __post_init__(self):\n"
            "        pl = self.placement\n"
            "        self._step = pl.donate_jit(self._step_impl,\n"
            "                                   donate_argnums=(1,))\n",
            # the choke point itself is allowed to build the jit
            "src/repro/serving/placement.py":
            "import jax\n"
            "def donate_jit(fn):\n"
            "    return jax.jit(fn)\n"}
    _, hit = rules_hit(good)
    assert "donate-jit-choke-point" not in hit


def test_choke_point_catches_decorator_and_from_import():
    bad = {"src/repro/serving/prefill.py":
           "from jax import jit\n"
           "import functools, jax\n"
           "@jit\n"
           "def f(x):\n"
           "    return x\n"
           "@functools.partial(jax.jit, static_argnums=(1,))\n"
           "def g(x, n):\n"
           "    return x\n"}
    rep, hit = rules_hit(bad)
    lines = {d.line for d in rep.diagnostics
             if d.rule == "donate-jit-choke-point"}
    assert lines == {3, 6}


def test_proxy_jax_free_direct_import():
    bad = {"src/repro/core/proxy/params.py": "import jax.numpy as jnp\n"}
    rep, hit = rules_hit(bad)
    assert "proxy-jax-free" in hit
    good = {"src/repro/core/proxy/params.py": "import numpy as np\n"}
    _, hit = rules_hit(good)
    assert "proxy-jax-free" not in hit


def test_proxy_jax_free_transitive_import():
    files = {
        "src/repro/core/proxy/oas.py":
            "from repro.serving.helper import f\n",
        "src/repro/serving/helper.py":
            # two hops: helper itself is jax-free but pulls in a module
            # that is not
            "from repro.serving.deep import g\ndef f():\n    pass\n",
        "src/repro/serving/deep.py": "import jax\ndef g():\n    pass\n"}
    rep, hit = rules_hit(files)
    assert "proxy-jax-free" in hit
    (d,) = [x for x in rep.diagnostics if x.rule == "proxy-jax-free"]
    assert "repro.serving.helper" in d.msg and "repro.serving.deep" in d.msg
    # numpy-only intra-repo deps stay clean
    ok = {"src/repro/core/proxy/oas.py":
          "from repro.core.proxy.radix import RadixTree\n",
          "src/repro/core/proxy/radix.py": "import numpy as np\n"}
    _, hit = rules_hit(ok)
    assert "proxy-jax-free" not in hit


def test_host_sync_item_and_int_in_impl():
    bad = {"src/repro/serving/decode.py":
           "class D:\n"
           "    def _step_impl(self, params, cache, state):\n"
           "        v = state['t'].item()\n"
           "        n = int(cache[0])\n"
           "        return v, n\n"}
    rep, hit = rules_hit(bad)
    lines = {d.line for d in rep.diagnostics
             if d.rule == "no-host-sync-in-impl"}
    assert lines == {3, 4}


def test_host_sync_allows_host_side_glue_and_static_args():
    good = {"src/repro/serving/decode.py":
            "import numpy as np\n"
            "class D:\n"
            "    def __post_init__(self):\n"
            "        self._r = pl.donate_jit(self._r_impl,\n"
            "                                static_argnums=(1,))\n"
            "    def _r_impl(self, x, n):\n"
            "        a = int(x.shape[0])\n"       # shapes are trace-time
            "        b = int(n) + len(x)\n"       # n is static, len is too
            "        return a + b\n"
            "    def step_host(self, out):\n"     # not a jitted body
            "        return int(np.asarray(out)[0])\n"}
    _, hit = rules_hit(good)
    assert "no-host-sync-in-impl" not in hit


def test_host_sync_device_get_asarray_block_until_ready():
    bad = {"src/repro/serving/arena.py":
           "import jax\n"
           "import numpy as np\n"
           "def _copy_impl(src, dst):\n"
           "    jax.device_get(src)\n"
           "    np.asarray(dst)\n"
           "    src.block_until_ready()\n"
           "    return dst\n"}
    rep, _ = rules_hit(bad)
    lines = {d.line for d in rep.diagnostics
             if d.rule == "no-host-sync-in-impl"}
    assert lines == {4, 5, 6}


def test_seeded_rng_only_pair():
    bad = {"src/repro/serving/sched.py":
           "import time, random\n"
           "import numpy as np\n"
           "def schedule():\n"
           "    return (time.time(), np.random.rand(3),\n"
           "            np.random.default_rng(), random.randint(0, 5))\n"}
    rep, hit = rules_hit(bad)
    assert len([d for d in rep.diagnostics
                if d.rule == "seeded-rng-only"]) == 4
    good = {"src/repro/serving/sched.py":
            "import time, random\n"
            "import numpy as np\n"
            "def schedule(seed):\n"
            "    rng = np.random.default_rng(seed)\n"
            "    r2 = random.Random(seed)\n"
            "    t = time.monotonic()\n"
            "    return rng, r2, t\n",
            # out of scope: launch/ may use wall-clock
            "src/repro/launch/bench.py":
            "import time\nt = time.time()\n"}
    _, hit = rules_hit(good)
    assert "seeded-rng-only" not in hit


def test_no_shape_leak_pair():
    src = ("class P:\n"
           "    def __post_init__(self):\n"
           "        self._resume = pl.donate_jit(self._resume_impl,\n"
           "                                     donate_argnums=(2,),\n"
           "                                     static_argnums=(5,))\n"
           "    def go(self, params, toks, cache, cl, tables, x):\n"
           "        bad = self._resume(params, toks, cache, cl, tables,\n"
           "                           {})\n")
    bad = {"src/repro/serving/prefill.py":
           src.replace("{}", "x.shape[0]")}
    rep, hit = rules_hit(bad)
    assert "no-shape-leak" in hit
    good = {"src/repro/serving/prefill.py":
            src.replace("{}", "_bucket(x.shape[0])")}
    _, hit = rules_hit(good)
    assert "no-shape-leak" not in hit


def test_repo_hygiene_tracked_artifacts_and_gitignore():
    rep, hit = rules_hit({}, tracked_files=["src/repro/__pycache__/x.pyc",
                                            "tests/.pytest_cache/v/cache",
                                            ".jax_cache/jit_step-abc123",
                                            ".hypothesis/constants/0f1e",
                                            "src/repro/core/oas.py"],
                         gitignore_text="")
    ds = [d for d in rep.diagnostics if d.rule == "repo-hygiene"]
    # 4 tracked artifacts + 4 missing .gitignore patterns
    assert len(ds) == 8 and "repo-hygiene" in hit
    rep, hit = rules_hit({}, tracked_files=["src/repro/core/oas.py"],
                         gitignore_text=GITIGNORE)
    assert "repo-hygiene" not in hit


# ---------------------------------------------------------------------------
# waiver handling
# ---------------------------------------------------------------------------

BAD_IMPL = ("class D:\n"
            "    def _step_impl(self, state):\n"
            "        {}\n"
            "        return state\n")


def test_waiver_downgrades_and_echoes_justification():
    files = {"src/repro/serving/decode.py": BAD_IMPL.format(
        "v = state.item()  # contract: waive no-host-sync-in-impl "
        "-- warmup-only probe, removed by DCE in the steady-state trace")}
    rep = run_lint(files=files, tracked_files=[],
                   gitignore_text=GITIGNORE)
    assert rep.ok() and rep.ok(strict=True)
    (d,) = rep.waived()
    assert d.justification.startswith("warmup-only probe")
    assert "warmup-only probe" in rep.format()  # report echoes the why


def test_waiver_on_line_above():
    files = {"src/repro/serving/decode.py": BAD_IMPL.format(
        "# contract: waive no-host-sync-in-impl -- fixture reason\n"
        "        v = state.item()")}
    rep = run_lint(files=files, tracked_files=[],
                   gitignore_text=GITIGNORE)
    assert rep.ok() and len(rep.waived()) == 1


def test_waiver_is_rule_and_line_narrow():
    # wrong rule id -> violation stays, waiver goes stale
    files = {"src/repro/serving/decode.py": BAD_IMPL.format(
        "v = state.item()  # contract: waive seeded-rng-only -- wrong rule")}
    rep = run_lint(files=files, tracked_files=[],
                   gitignore_text=GITIGNORE)
    assert not rep.ok()
    assert any(d.rule == "stale-waiver" for d in rep.errors(strict=True))


def test_waiver_without_justification_fails_strict():
    files = {"src/repro/serving/decode.py": BAD_IMPL.format(
        "v = state.item()  # contract: waive no-host-sync-in-impl")}
    rep = run_lint(files=files, tracked_files=[],
                   gitignore_text=GITIGNORE)
    assert rep.ok() and not rep.ok(strict=True)  # CI (--strict) still fails
    assert any(d.rule == "waiver-missing-justification"
               for d in rep.errors(strict=True))


# ---------------------------------------------------------------------------
# the real tree is contract-clean (what `python -m repro.analysis --strict`
# gates in CI)
# ---------------------------------------------------------------------------

def test_real_tree_is_contract_clean():
    rep = run_lint()
    assert rep.ok(strict=True), rep.format(strict=True)


# ---------------------------------------------------------------------------
# HotLoopRegistry completeness: every donate_jit call site in serving/
# shows up in the registry of a constructed server
# ---------------------------------------------------------------------------

def _donate_jit_call_sites():
    """Scrape serving/*.py for the fn names handed to donate_jit."""
    names = set()
    for f in sorted((REPO / "src/repro/serving").glob("*.py")):
        for node in ast.walk(ast.parse(f.read_text())):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "donate_jit" and node.args:
                tgt = node.args[0]
                if isinstance(tgt, ast.Attribute):
                    names.add(tgt.attr)
                elif isinstance(tgt, ast.Name):
                    names.add(tgt.id)
    return names


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced_config("qwen2-1.5b").with_updates(
        compute_dtype="float32", param_dtype="float32", n_layers=2)
    rng = np.random.default_rng(0)
    reqs = [(tuple(rng.integers(0, cfg.vocab_size, 9).tolist()), 5),
            (tuple(rng.integers(0, cfg.vocab_size, 17).tolist()), 5)]
    return cfg, reqs


def test_registry_covers_every_serving_donate_jit_site(tiny):
    from repro.core.placement.migration import MigrationPlan
    from repro.serving import Server, ServerConfig
    from repro.serving.spec import SpecConfig
    cfg, reqs = tiny
    sites = _donate_jit_call_sites()
    assert sites, "scrape found no donate_jit call sites?"

    registered = set()
    # paged + spec server: paged insert/extract, step, verify, arenas
    srv = Server(cfg, ServerConfig(decode_slots=4, max_len=96,
                                   spec=SpecConfig()), pattern=[0, 0])
    registered |= {n.split(".")[-1] for n in srv.placement.hot_loops.names()}
    # dense server: dense insert/extract
    srv = Server(cfg, ServerConfig(decode_slots=4, max_len=96,
                                   paged_kv=False), pattern=[0, 0])
    registered |= {n.split(".")[-1] for n in srv.placement.hot_loops.names()}
    # MoE server + one forced migration: the lazily-built remap jit
    mcfg = reduced_config("qwen2-moe-a2.7b").with_updates(
        n_layers=2, compute_dtype="float32", param_dtype="float32")
    msrv = Server(mcfg, ServerConfig(decode_slots=2, max_len=64))
    old_se = np.asarray(msrv.tables["slot_expert"]).copy()
    new_se = old_se.copy()
    new_se[0, 0], new_se[0, 1] = old_se[0, 1], old_se[0, 0]
    msrv._apply_migration(MigrationPlan(old_se, new_se, ((0, 0, 0),), 1))
    registered |= {n.split(".")[-1]
                   for n in msrv.placement.hot_loops.names()}

    missing = sites - registered
    assert not missing, \
        f"donate_jit call sites never registered: {sorted(missing)}"


def test_registry_entry_metadata(tiny):
    from repro.serving import Server, ServerConfig
    cfg, reqs = tiny
    srv = Server(cfg, ServerConfig(decode_slots=4, max_len=96),
                 pattern=[0, 0])
    by_name = {e.name.split(".")[-1]: e
               for e in srv.placement.hot_loops.entries}
    step = by_name["_step_impl"]
    assert step.donate_argnums == (1, 2) and step.out_specs is not None
    assert step.calls == 0 and step.abstract_args is None
    srv.run(reqs)
    assert step.calls > 0 and step.abstract_args is not None


# ---------------------------------------------------------------------------
# layer 2: jaxpr audit over live servers
# ---------------------------------------------------------------------------

@pytest.mark.jaxpr_audit
def test_audit_one_device_server(tiny):
    from repro.serving import Server, ServerConfig
    from repro.serving.spec import SpecConfig
    cfg, reqs = tiny
    for scfg in (ServerConfig(decode_slots=4, max_len=96),
                 ServerConfig(decode_slots=4, max_len=96,
                              spec=SpecConfig())):
        srv = Server(cfg, scfg, pattern=[0, 0])
        srv.run(reqs)
        rep = srv.audit_hot_loops()
        assert rep.ok(), rep.format()
        # the decode hot loop must have been audited, with its donation
        # verified on the lowered module
        assert any("_step_impl" in n or "_verify_impl" in n
                   for n in rep.audited)
        assert rep.checks.get("donation", 0) >= 1
        assert rep.checks.get("purity", 0) >= 1
        assert rep.checks.get("arena-copy", 0) >= 1


@pytest.mark.jaxpr_audit
@pytest.mark.parametrize("plane", ["bf16", "int8", "spec"])
def test_audit_arena_copy_four_layer_server(tiny, plane):
    """Four layers scanned as one period repeated four times: the decode
    step (or verify) and the paged prefill chunk update the stacked arenas
    in place — no whole-stack or whole-layer copy, broadcast, slice or
    slice update of any arena leaf in the compiled programs."""
    from repro.serving import Server, ServerConfig
    from repro.serving.quant import QuantConfig
    from repro.serving.spec import SpecConfig
    cfg, reqs = tiny
    cfg = cfg.with_updates(n_layers=4)
    kw = {"int8": {"quant": QuantConfig()}, "spec": {"spec": SpecConfig()},
          "bf16": {}}[plane]
    srv = Server(cfg, ServerConfig(decode_slots=4, max_len=96, **kw),
                 pattern=[0] * 4)
    assert srv.lm.plan.n_rep == 4
    rng = np.random.default_rng(1)
    srv.run(reqs + [(tuple(rng.integers(0, cfg.vocab_size, 40).tolist()),
                     5)])
    rep = srv.audit_hot_loops()
    assert rep.ok(), rep.format()
    audited = {n.split(".")[-1] for n in rep.audited}
    step = "_verify_impl" if plane == "spec" else "_step_impl"
    assert {step, "_resume_paged_impl"} <= audited, rep.format()
    assert rep.checks.get("arena-copy", 0) >= 2


@pytest.mark.jaxpr_audit
def test_audit_catches_arena_scanned_as_xs_ys(tiny):
    """Negative control: a layer scan that takes a stacked arena as xs and
    returns it as ys (each layer sliced out, written, and stacked back into
    a fresh buffer) is flagged by arena-copy; the same update with the
    arena in the carry, indexed by the layer counter, passes."""
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import audit_placement
    from repro.serving import DevicePlacement
    pl = DevicePlacement.local()
    R, N, K, bs, h = 4, 9, 2, 4, 8

    def xs_ys(cache, blk, rows):
        def body(c, xs):
            k, row = xs
            return c, k.at[blk, :, 0].set(row)
        _, k = jax.lax.scan(body, 0, (cache["period"][0]["k"], rows))
        return {"period": ({"k": k},)}

    def carried(cache, blk, rows):
        def body(k, xs):
            i, row = xs
            return k.at[i, blk, :, 0].set(row), None
        k, _ = jax.lax.scan(body, cache["period"][0]["k"],
                            (jnp.arange(R), rows))
        return {"period": ({"k": k},)}

    # the registry holds entries weakly: keep the jits alive to audit them
    jits = [pl.donate_jit(f, donate_argnums=(0,)) for f in (xs_ys, carried)]
    for f in jits:
        f({"period": ({"k": jnp.zeros((R, N, K, bs, h))},)}, jnp.int32(3),
          jnp.ones((R, K, h)))
    rep = audit_placement(pl)
    flagged = {f.entry.split(".")[-1] for f in rep.findings
               if f.check == "arena-copy"}
    assert flagged == {"xs_ys"}, rep.format()
    assert rep.checks.get("arena-copy") == 2


@pytest.mark.jaxpr_audit
def test_audit_catches_callback_and_dropped_donation(tiny):
    """Negative control: a hot loop with a debug callback and one whose
    donation cannot alias must both be flagged."""
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import audit_placement
    from repro.serving import DevicePlacement
    pl = DevicePlacement.local()

    def noisy(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    def no_alias(x):  # f32 in, i32 out: donated buffer can't be reused
        return (x * 2).astype(jnp.int32)

    noisy_jit = pl.donate_jit(noisy)
    drop_jit = pl.donate_jit(no_alias, donate_argnums=(0,))
    noisy_jit(jnp.ones((4,), jnp.float32))
    drop_jit(jnp.ones((512, 512), jnp.float32))
    rep = audit_placement(pl)
    checks = {(f.entry.split(".")[-1], f.check) for f in rep.findings}
    assert ("noisy", "purity") in checks, rep.format()
    assert ("no_alias", "donation") in checks, rep.format()


@pytest.mark.jaxpr_audit
def test_audit_catches_f64_convert(tiny):
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import audit_placement
    from repro.serving import DevicePlacement
    jax.config.update("jax_enable_x64", True)
    try:
        pl = DevicePlacement.local()
        f64_jit = pl.donate_jit(lambda x: x.astype(jnp.float64).sum())
        f64_jit(jnp.ones((4,), jnp.float32))
        rep = audit_placement(pl)
    finally:
        jax.config.update("jax_enable_x64", False)
    assert any(f.check == "f64" for f in rep.findings), rep.format()


@pytest.mark.jaxpr_audit
def test_audit_quant_server_no_upcast(tiny):
    """Quantized server: every called hot loop that touches int8 arena
    leaves passes the quant-upcast check — no full-arena f32 twin is ever
    materialized (dequant stays in-tile / on gathered views)."""
    from repro.serving import Server, ServerConfig
    from repro.serving.quant import QuantConfig
    cfg, reqs = tiny
    srv = Server(cfg, ServerConfig(decode_slots=4, max_len=96,
                                   quant=QuantConfig()), pattern=[0, 0])
    srv.run(reqs)
    rep = srv.audit_hot_loops()
    assert rep.ok(), rep.format()
    assert rep.checks.get("quant-upcast", 0) >= 1, \
        "no hot loop carried int8 arena leaves — check never armed"


@pytest.mark.jaxpr_audit
def test_audit_catches_full_arena_dequant(tiny):
    """Negative control: a hot loop that dequantizes the ENTIRE int8
    arena into an f32 twin must be flagged by quant-upcast, while a
    gathered-view dequant (tabled blocks only) must pass."""
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import audit_placement
    from repro.serving import DevicePlacement
    pl = DevicePlacement.local()

    def upcast(pages, scale):        # [N,K,bs,h] int8 → full f32 twin
        return (pages.astype(jnp.float32)
                * scale[:, :, None, :]).sum()

    def gathered(pages, scale, tables):   # dequant only tabled blocks
        g = pages[tables].astype(jnp.float32)
        return (g * scale[tables][:, :, :, None, :]).sum()

    N, K, bs, h = 16, 2, 8, 4
    pages = jnp.zeros((N, K, bs, h), jnp.int8)
    scale = jnp.ones((N, K, h), jnp.float32)
    tables = jnp.zeros((2, 3), jnp.int32)
    # the registry holds entries weakly: keep the jits alive to audit them
    upcast_jit, gathered_jit = pl.donate_jit(upcast), pl.donate_jit(gathered)
    upcast_jit(pages, scale)
    gathered_jit(pages, scale, tables)
    rep = audit_placement(pl)
    flagged = {f.entry.split(".")[-1] for f in rep.findings
               if f.check == "quant-upcast"}
    assert flagged == {"upcast"}, rep.format()


@pytest.mark.jaxpr_audit
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8")
def test_audit_tp2_ep4_server_out_shardings(tiny):
    """Acceptance: donation + pinned out-shardings verified for every
    called hot-loop jit of a tp=2,ep=4 server (device-forced CPU mesh)."""
    from repro.models import LM
    from repro.serving import DevicePlacement, Server, ServerConfig
    _, reqs = tiny
    cfg = reduced_config("qwen2-moe-a2.7b").with_updates(
        compute_dtype="float32", param_dtype="float32")
    pl1 = DevicePlacement.local()
    lm1 = LM.build(cfg, pl1.ctx)
    params1 = lm1.init(jax.random.PRNGKey(0))
    pl8 = DevicePlacement.build(tp=2, ep=4)
    lm8 = LM.build(cfg, pl8.ctx)
    params8 = pl8.transfer_params(lm1, params1, lm8)
    srv = Server(cfg, ServerConfig(decode_slots=4, max_len=96),
                 placement=pl8, params=params8)
    rng = np.random.default_rng(3)
    srv.run([(tuple(rng.integers(0, cfg.vocab_size, 9).tolist()), 5),
             (tuple(rng.integers(0, cfg.vocab_size, 17).tolist()), 5)])
    rep = srv.audit_hot_loops()
    assert rep.ok(), rep.format()
    # every audited entry that pins out_specs had its compiled output
    # shardings compared against the placement's own spec tree
    pinned = [e for e in srv.placement.hot_loops.called()
              if e.out_specs is not None]
    assert pinned and rep.checks.get("out-shardings", 0) == len(pinned)
    assert rep.checks.get("donation", 0) >= 1
