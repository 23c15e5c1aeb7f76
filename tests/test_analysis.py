"""Static-analysis layer: spec diagnostics, lint rules, retrace guard.

Three contracts under test (see ``docs/architecture.md`` "Static analysis"):

* ``spac check`` flags each seeded bad-fixture spec with its documented
  ``SPAC1xx`` code and comes back clean on every registry scenario;
* ``spaclint`` rules fire on minimal positive fixtures (including a
  reproduction of the PR 3 shared-mutable-default bug), honour suppression
  comments, and find nothing in the repo itself;
* the retrace guard proves the stage-2/stage-4 engines compile exactly once
  per (shape, mesh) — at one device in-process and at two forced devices in
  a subprocess.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, os.path.join(REPO, "src"))

from repro.analysis import lint as lint_mod
from repro.analysis.check import check_scenario
from repro.analysis.diagnostics import (Diagnostic, exit_code,
                                        to_json_payload, worst_severity)
from repro.analysis.retrace import RetraceError, retrace_guard
from repro.api.cli import main as cli_main
from repro.api.registry import registry
from repro.core.dse import SLA, ResourceBudget


# --------------------------------------------------------------------------
# spac check: the four seeded bad fixtures + registry cleanliness
# --------------------------------------------------------------------------

def _with_protocol_params(scenario, **params):
    proto = dataclasses.replace(scenario.protocol,
                                params={**scenario.protocol.params, **params})
    return dataclasses.replace(scenario, protocol=proto)


def _codes(diags):
    return {d.code for d in diags}


def test_check_underaddressed_routing_field():
    """hft has 8 ports; a 2-bit dst/src cannot address them -> SPAC101."""
    bad = _with_protocol_params(registry["hft"], addr_bits=2)
    diags = check_scenario(bad)
    hits = [d for d in diags if d.code == "SPAC101"]
    assert {d.location for d in hits} == {"protocol.dst", "protocol.src"}
    assert all(d.severity == "error" and d.hint for d in hits)
    assert exit_code(diags) == 1


def test_check_unsatisfiable_sla():
    """p99 of 1 ns sits below every pipeline's analytic floor -> SPAC102."""
    bad = dataclasses.replace(registry["hft"], sla=SLA(p99_latency_ns=1.0))
    diags = check_scenario(bad)
    assert "SPAC102" in _codes(diags)
    (hit,) = [d for d in diags if d.code == "SPAC102"]
    assert hit.severity == "error"
    assert hit.location == "sla.p99_latency_ns"
    assert "lower bound" in hit.message


def test_check_sla_throughput_facet():
    bad = dataclasses.replace(registry["hft"],
                              sla=SLA(min_throughput_gbps=1e6))
    assert "SPAC102" in _codes(check_scenario(bad))


def test_check_overbudget_resource_request():
    """10 LUTs is under the cheapest depth-1 candidate -> SPAC103 error;
    a key the resource model never produces -> SPAC103 warning."""
    bad = dataclasses.replace(registry["hft"],
                              budget=ResourceBudget({"luts": 10.0,
                                                     "gates": 1.0}))
    diags = [d for d in check_scenario(bad) if d.code == "SPAC103"]
    assert {d.severity for d in diags} == {"error", "warning"}
    assert any(d.location.endswith(".luts") and d.severity == "error"
               for d in diags)
    assert any(d.location.endswith(".gates") and d.severity == "warning"
               for d in diags)


def test_check_dead_codesign_gene():
    """datacenter has 32 ports; an addr menu of (2, 4) bits leaves no live
    width -> SPAC104 dead gene, and the whole layout space dies -> SPAC105."""
    base = registry["datacenter"]
    wide = dataclasses.replace(base, protocol=base.protocol.widen())
    dead = _with_protocol_params(wide, addr_bits=(2, 4))
    diags = check_scenario(dead)
    d104 = [d for d in diags if d.code == "SPAC104"]
    assert any(d.location == "protocol.dst" and d.severity == "error"
               for d in d104)
    d105 = [d for d in diags if d.code == "SPAC105"]
    assert d105 and d105[0].severity == "error"
    assert "0 of" in d105[0].message


def test_check_codesign_space_is_info_only():
    """A healthy widened space reports size/fraction as info, exit 0."""
    base = registry["datacenter"]
    wide = dataclasses.replace(base, protocol=base.protocol.widen())
    diags = check_scenario(wide)
    assert _codes(diags) == {"SPAC105"}
    assert worst_severity(diags) == "info"
    assert exit_code(diags) == 0


def test_check_fabric_topology_addressability():
    """SPAC106: swapping fattree_dc's k=4 topology for k=8 makes 32 hosts —
    the 4-bit routing/src fields, the 4-port tier template, and the 8-id
    trace all stop matching the fabric, and each mismatch is named."""
    from repro.api.scenario import TopologySpec
    bad = dataclasses.replace(
        registry["fattree_dc"], name="bad_fabric",
        topology=TopologySpec.make("fattree", k=8))
    diags = [d for d in check_scenario(bad) if d.code == "SPAC106"]
    assert all(d.severity == "error" and d.hint for d in diags)
    locs = {d.location for d in diags}
    # routing + src addressability vs the *host* count, not n_ports
    assert {"protocol.dst", "protocol.src"} <= locs
    assert any("32 hosts" in d.message for d in diags)
    # tier degree vs the arch template (both tiers of a k=8 tree have deg 8)
    assert sum(1 for d in diags if d.location == "arch.n_ports") == 2
    # trace endpoint ids vs the host count
    assert "trace.n_ports" in locs


def test_check_fabric_codesign_space_addressability():
    """The space path of SPAC106: a widened protocol whose every routing
    width is narrower than the host count is a dead fabric gene."""
    from repro.api.scenario import TopologySpec
    base = registry["fattree_dc"]
    wide = dataclasses.replace(base, protocol=base.protocol.widen())
    # 3-bit max routing addresses 8 hosts of k=4; k=8's 32 hosts need 5 bits
    dead = _with_protocol_params(
        dataclasses.replace(wide, name="bad_fabric_space",
                            topology=TopologySpec.make("fattree", k=8)),
        addr_bits=(2, 4))
    hits = [d for d in check_scenario(dead) if d.code == "SPAC106"
            and d.location == "protocol.dst"]
    assert hits and "no width choice" in hits[0].message


def test_check_registry_all_clean():
    """Acceptance: every registered workload (switch and comm) exits 0."""
    for name in registry.names():
        diags = check_scenario(registry[name])
        assert exit_code(diags) == 0, (name, [d.format() for d in diags])


def test_diagnostic_record_shape():
    d = Diagnostic("SPAC101", "error", "msg", "protocol.dst", hint="widen")
    assert d.to_dict() == {"code": "SPAC101", "severity": "error",
                           "message": "msg", "location": "protocol.dst",
                           "hint": "widen"}
    assert "hint: widen" in d.format()
    with pytest.raises(ValueError):
        Diagnostic("SPAC101", "fatal", "msg", "loc")
    payload = to_json_payload([d])
    assert payload["exit_code"] == 1 and payload["worst_severity"] == "error"


# --------------------------------------------------------------------------
# spac check CLI: exit codes 0 / 1 / 2, no tracebacks
# --------------------------------------------------------------------------

def test_check_cli_clean_and_json(capsys):
    assert cli_main(["check", "hft", "grad_bucket"]) == 0
    assert "clean" in capsys.readouterr().out
    assert cli_main(["check", "hft", "--format", "json"]) == 0
    assert '"exit_code": 0' in capsys.readouterr().out


def test_check_cli_findings_from_fixture_file(tmp_path, capsys):
    bad = _with_protocol_params(registry["hft"], addr_bits=2)
    path = tmp_path / "bad_hft.json"
    path.write_text(bad.to_json())
    assert cli_main(["check", str(path)]) == 1
    out = capsys.readouterr().out
    assert "SPAC101" in out and str(path) in out


def test_check_cli_usage_errors(tmp_path, capsys):
    assert cli_main(["check", "no_such_scenario"]) == 2
    assert "unknown scenario" in capsys.readouterr().err
    bad = tmp_path / "malformed.json"
    bad.write_text("{ not json")
    assert cli_main(["check", str(bad)]) == 2
    assert "cannot load" in capsys.readouterr().err
    with pytest.raises(SystemExit) as e:
        cli_main(["check", "hft", "--format", "yaml"])
    assert e.value.code == 2


# --------------------------------------------------------------------------
# spaclint rules: positive + suppressed fixtures
# --------------------------------------------------------------------------

def _lint(src, **kw):
    return lint_mod.lint_source(textwrap.dedent(src), filename="fix.py", **kw)


def test_lint_parse_error_is_spac200():
    (d,) = _lint("def f(:\n")
    assert d.code == "SPAC200" and d.severity == "error"


def test_lint_mutable_default_pr3_reproduction():
    """The exact PR 3 bug class: a constructed config as a default."""
    diags = _lint("""
        def run_netsim(arch, cfg: NetSimConfig = NetSimConfig()):
            return cfg
    """)
    assert [d.code for d in diags] == ["SPAC201"]
    assert "NetSimConfig" in diags[0].message and "None" in diags[0].hint


def test_lint_mutable_default_literals_and_exemptions():
    assert [d.code for d in _lint("def f(xs=[]): pass")] == ["SPAC201"]
    assert [d.code for d in _lint("def f(m={}): pass")] == ["SPAC201"]
    assert [d.code for d in _lint("def f(*, s=set()): pass")] == ["SPAC201"]
    assert _lint("def f(t=(1, 2), fs=frozenset({1}), x=None): pass") == []


def test_lint_global_np_random():
    diags = _lint("""
        import numpy as np
        x = np.random.rand(3)
    """)
    assert [d.code for d in diags] == ["SPAC202"]
    assert _lint("import numpy as np\nrng = np.random.default_rng(0)\n") == []


def test_lint_wallclock_report_key():
    diags = _lint("""
        import time
        def bench():
            t0 = time.time()
            work()
            return {"wall_s": time.time() - t0,
                    "wall_time_s": time.time() - t0}
    """)
    assert [d.code for d in diags] == ["SPAC203"]
    assert "'wall_s'" in diags[0].message
    # division launders: a rate derived from a timestamp is not a timestamp
    assert _lint("""
        import time
        def bench(n):
            t0 = time.time()
            return {"rows_per_sec": n / (time.time() - t0)}
    """) == []


def test_lint_wallclock_subscript_assignment():
    diags = _lint("""
        import time
        def bench(rec):
            t0 = time.perf_counter()
            rec["elapsed"] = time.perf_counter() - t0
            rec["elapsed_time_s"] = time.perf_counter() - t0
    """)
    assert [d.code for d in diags] == ["SPAC203"]


def test_lint_set_iteration():
    assert [d.code for d in _lint("for d in {1, 2, 3}:\n    use(d)\n")] \
        == ["SPAC204"]
    assert [d.code for d in _lint("xs = list({f(d) for d in ds})\n")] \
        == ["SPAC204"]
    assert _lint("for d in sorted({1, 2, 3}):\n    use(d)\n") == []


def test_lint_jit_closing_over_mutable_global():
    diags = _lint("""
        import jax
        STATE = {"k": 1}
        @jax.jit
        def f(x):
            return x + STATE["k"]
    """)
    assert [d.code for d in diags] == ["SPAC205"]
    assert _lint("""
        import jax
        STATE = (1, 2)
        @jax.jit
        def f(x):
            return x + STATE[0]
    """) == []


def test_lint_unscoped_x64():
    assert [d.code for d in _lint("enable_x64()\n")] == ["SPAC206"]
    assert [d.code for d in
            _lint("config.update('jax_enable_x64', True)\n")] == ["SPAC206"]
    assert _lint("with enable_x64():\n    run()\n") == []


def test_lint_jit_in_loop():
    diags = _lint("""
        import jax
        for i in range(3):
            f = jax.jit(lambda x: x + i)
    """)
    assert [d.code for d in diags] == ["SPAC207"]
    # the builder idiom (one jit per static config) is the sanctioned fix
    assert _lint("""
        import jax
        def build(i):
            return jax.jit(lambda x: x + i)
        for i in range(3):
            f = build(i)
    """) == []


def test_lint_sort_in_loop():
    diags = _lint("""
        import numpy as np
        for b in range(8):
            ends = np.sort(dep[b])
    """)
    assert [d.code for d in diags] == ["SPAC208"]
    assert "loop body" in diags[0].message
    # the For iterable is evaluated once — a sort there is not per-iteration
    assert _lint("""
        import numpy as np
        for k in np.argsort(times, kind="stable"):
            use(k)
    """) == []
    # batch-axis sort outside the loop is the sanctioned fix
    assert _lint("""
        import numpy as np
        ends = np.sort(dep, axis=1)
        for b in range(8):
            use(ends[b])
    """) == []
    # a while condition re-evaluates every iteration, so it does count
    assert [d.code for d in _lint("""
        import numpy as np
        while np.lexsort((a, b))[0] != 0:
            step()
    """)] == ["SPAC208"]


def test_lint_suppression_comment():
    line = "def f(xs=[]):  # spaclint: disable=SPAC201\n    pass\n"
    assert _lint(line) == []
    bare = "def f(xs=[]):  # spaclint: disable\n    pass\n"
    assert _lint(bare) == []
    wrong = "def f(xs=[]):  # spaclint: disable=SPAC204\n    pass\n"
    assert [d.code for d in _lint(wrong)] == ["SPAC201"]


def test_lint_select_filter():
    src = "def f(xs=[]):\n    pass\nfor d in {1, 2}:\n    use(d)\n"
    assert [d.code for d in _lint(src, select={"SPAC204"})] == ["SPAC204"]


def test_lint_cli_exit_codes(tmp_path, capsys):
    assert lint_mod.main(["--list-rules"]) == 0
    assert "SPAC201" in capsys.readouterr().out
    assert lint_mod.main(["--select", "SPAC999", str(tmp_path)]) == 2
    assert lint_mod.main([str(tmp_path / "nope")]) == 2
    dirty = tmp_path / "dirty.py"
    dirty.write_text("def f(xs=[]):\n    pass\n")
    capsys.readouterr()
    assert lint_mod.main([str(dirty)]) == 1
    assert "SPAC201" in capsys.readouterr().out
    assert cli_main(["lint", str(dirty), "--select", "SPAC204"]) == 0


def test_repo_lints_clean():
    """Satellite (a): every violation the rules found was fixed, not
    suppressed — the whole repo must come back empty."""
    paths = [os.path.join(REPO, d) for d in ("src", "tests", "benchmarks")]
    diags = lint_mod.lint_paths(paths)
    assert diags == [], "\n".join(d.format() for d in diags)


# --------------------------------------------------------------------------
# retrace guard: one compile per (shape, mesh)
# --------------------------------------------------------------------------

def test_retrace_guard_single_device():
    from repro.core import (ArchRequest, bind, compressed_protocol,
                            enumerate_candidates)
    from repro.sim import run_netsim_batched, run_surrogate_batched
    from repro.traces import hft

    bound = bind(compressed_protocol(addr_bits=4, length_bits=6),
                 flit_bits=256)
    wide = enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:9]
    cands = wide[:3]
    # a duration no other test uses -> a fresh event-count shape, so the
    # expectations hold regardless of what ran earlier in the suite
    tr = hft(seed=0, duration_s=6.7e-5)

    with retrace_guard(expect=1) as g:
        run_surrogate_batched(cands, bound, tr, back_annotation=False)
    assert g.deltas() == {"surrogate.engine": 1}
    with retrace_guard(expect=0):
        run_surrogate_batched(cands, bound, tr, back_annotation=False)
    with retrace_guard(expect=0):        # same bucket width (8 rows)
        run_surrogate_batched(cands[:2], bound, tr, back_annotation=False)
    with retrace_guard(expect=1):        # next bucket (16) -> exactly one more
        run_surrogate_batched(wide, bound, tr, back_annotation=False)

    with retrace_guard(expect=1) as g:
        run_netsim_batched(cands, bound, tr, back_annotation=False)
    assert g.deltas() == {"netsim.engine": 1}
    with retrace_guard(expect=0):
        run_netsim_batched(cands, bound, tr, back_annotation=False)


def test_retrace_guard_raises_on_mismatch():
    with pytest.raises(RetraceError, match="expected exactly 3"):
        with retrace_guard(expect=3):
            pass


def test_unreadable_jit_cache_raises(monkeypatch):
    """A tracked callable without a jit cache must fail the guard loudly —
    reading it as 0 would let a zero-compilations check pass unseen."""
    from repro.analysis import retrace

    monkeypatch.setitem(retrace._TRACKED, "not.jitted", lambda x: x)
    with pytest.raises(TypeError, match="no jit cache size"):
        retrace.compile_counts()


def _run_forced(code, devices=2):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def _forced_devices_work():
    try:
        out = _run_forced("import jax; print(jax.device_count())")
    except AssertionError:
        return False
    return out.strip().endswith("2")


def test_retrace_guard_sharded_two_devices():
    """Acceptance: the lru-cached sharded builders compile exactly once per
    (shape, mesh) at 2 devices, and repeat calls add zero."""
    if not _forced_devices_work():
        pytest.skip("cannot force 2 simulated host devices on this backend")
    out = _run_forced(textwrap.dedent("""
        from repro.core import (ArchRequest, bind, compressed_protocol,
                                enumerate_candidates)
        from repro.sim import run_netsim_batched, run_surrogate_batched
        from repro.launch.mesh import MeshSpec
        from repro.traces import hft
        from repro.analysis.retrace import retrace_guard

        bound = bind(compressed_protocol(addr_bits=4, length_bits=6),
                     flit_bits=256)
        cands = enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:4]
        tr = hft(seed=0, duration_s=6.7e-5)
        mesh = MeshSpec(devices=2)

        with retrace_guard(expect=1) as g:
            run_surrogate_batched(cands, bound, tr, back_annotation=False,
                                  mesh=mesh)
        (name,) = g.deltas()
        assert name.startswith("surrogate.sharded["), name
        with retrace_guard(expect=0):
            run_surrogate_batched(cands, bound, tr, back_annotation=False,
                                  mesh=mesh)

        with retrace_guard(expect=1) as g:
            run_netsim_batched(cands, bound, tr, back_annotation=False,
                               mesh=mesh)
        (name,) = g.deltas()
        assert name.startswith("netsim.sharded["), name
        with retrace_guard(expect=0):
            run_netsim_batched(cands, bound, tr, back_annotation=False,
                               mesh=mesh)
        print("ok")
    """))
    assert out.strip().endswith("ok")
