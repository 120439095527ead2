"""filolint self-enforcement (tier-1, pure AST — no device, no TPU).

Three layers:
  1. fixture self-tests — every rule has a known-bad snippet it MUST flag and
     a known-good twin it must NOT (guards the analyzer against rotting into
     a no-op);
  2. repo enforcement — the filodb_tpu package analyzes to ZERO new findings
     (inline suppressions and the checked-in baseline are the only escape
     hatches);
  3. runtime hook parity — the statically declared lock order matches
     diagnostics.LOCK_ORDER, and the FILODB_LOCK_DEBUG assertion actually
     fires on an out-of-order acquisition.
"""

from __future__ import annotations

import ast
from pathlib import Path

import pytest

from filodb_tpu.analysis import Baseline, analyze_file, run_analysis
from filodb_tpu.analysis.findings import Finding, is_suppressed, \
    load_suppressions
from filodb_tpu.analysis.lockcheck import LOCK_ORDER as STATIC_LOCK_ORDER
from filodb_tpu.analysis.wirecheck import WireChecker
from filodb_tpu.utils import diagnostics
from filodb_tpu.utils.diagnostics import LOCK_ORDER as RUNTIME_LOCK_ORDER

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "filolint"

# fixture -> the rule(s) its bad twin MUST trip
BAD_FIXTURES = {
    "bad_lock_call.py": {"lock-unheld-call"},
    "bad_lock_write.py": {"lock-unheld-write"},
    "bad_lock_guard.py": {"lock-guard-inconsistent"},
    "bad_lock_order.py": {"lock-order", "lock-order-cycle"},
    "bad_jit_sync.py": {"jit-host-sync"},
    "bad_jit_branch.py": {"jit-traced-branch"},
    "bad_jit_closure.py": {"jit-mutable-closure"},
    "bad_jit_static.py": {"jit-static-args"},
    "bad_jit_donation.py": {"jit-donation-unused"},
    # v2 interprocedural families (resource lifecycle / except-flow /
    # declared surface / inherited-holder lockcheck)
    "bad_thread_leak.py": {"resource-thread-no-stop",
                           "resource-server-no-stop"},
    "bad_thread_loop.py": {"resource-worker-silent-death"},
    "bad_resource_release.py": {"resource-no-release"},
    # PR 6: transitive socket ownership (replication link pools) — an
    # instantiated owner-class instance stored on self needs a reachable
    # close()/stop()
    "bad_owned_resource.py": {"resource-no-release"},
    "bad_except_swallow.py": {"except-swallow", "except-overbroad-typed",
                              "except-state-leak"},
    "bad_config_key.py": {"surface-config-undeclared",
                          "surface-config-unused"},
    # PR 11: default-vs-type parity inside CONFIG_SPEC itself (the rules
    # subsystem grew the spec; this keeps every entry's default honest)
    "bad_config_type.py": {"surface-config-type"},
    "bad_metric_dup.py": {"surface-metric-duplicate",
                          "surface-metric-undeclared",
                          "surface-metric-kind"},
    "bad_lock_helper.py": {"lock-unheld-call"},
    # PR 7: declared span surface (TRACE_SPEC, mirroring CONFIG/METRICS)
    "bad_trace_span.py": {"surface-trace-undeclared",
                          "surface-trace-unused"},
    # PR 8: bounded-cache contract — every *Cache class needs a capacity
    # bound and eviction accounting (plan cache / result cache set the bar)
    "bad_bounded_cache.py": {"surface-cache-unbounded",
                             "surface-cache-no-eviction-metric"},
    # PR 13: byte-bound extension — a cache that accounts bytes holds
    # variable-size entries and must also declare a byte capacity (the
    # incremental fragment cache set this contract)
    "bad_cache_bytes.py": {"surface-cache-unbounded-bytes"},
    # PR 15: vectorized-ops-only contract of the columnar index modules —
    # a per-element Python loop over posting arrays in core/index*.py is
    # the 1M-series bottleneck the columnar engine exists to prevent
    "bad_index_postings.py": {"index-pure-python-postings"},
    # PR 16: one-program mesh queries — a jit/pjit boundary in parallel/
    # crossed by sharded store operands must declare BOTH in_shardings and
    # out_shardings, or jax silently re-gathers the globals per dispatch
    "bad_mesh_sharding.py": {"mesh-sharding-undeclared"},
    # PR 17: universal compressed residency — every decode variant in
    # ops/decodereg.py must register BOTH backend twins (pallas= and xla=,
    # neither None), or variant parity breaks when query.fused_kernels
    # flips the serving backend
    "bad_decode_variant.py": {"surface-decode-variant-twin"},
    # PR 18: epoch & visibility contracts — every mutation of query-visible
    # store state must be a declared EPOCH_SPEC site (or reachable only
    # from one), bump-fenced on every CFG path, under the shard lock, with
    # an honest affected-ts; the read side must capture the epoch vector
    # BEFORE execution and validate with that capture
    "bad_epoch_visibility.py": {"epoch-undeclared-visibility",
                                "epoch-bump-uncovered"},
    "bad_epoch_bump.py": {"epoch-bump-unlocked", "epoch-bump-overclaim"},
    "bad_epoch_probe.py": {"epoch-capture-after-execute",
                           "epoch-validate-refetched"},
    # PR 18: an inline ignore whose rule no longer fires is itself a
    # finding — it would silently swallow whatever fires there next
    "bad_stale_ignore.py": {"filolint-stale-ignore"},
    # PR 20: liveness & bounded-wait contracts (LATENCY_SPEC) — no
    # blocking under a declared lock, deadline-bounded socket I/O,
    # bounded+paced retry loops, timeout-carrying waits
    "bad_live_block.py": {"live-block-under-lock"},
    "bad_live_io.py": {"live-unbounded-io"},
    "bad_live_retry.py": {"live-unbounded-retry"},
    "bad_live_wait.py": {"live-wait-no-timeout"},
}


# -- 1. fixture self-tests ---------------------------------------------------

@pytest.mark.parametrize("name,rules", sorted(BAD_FIXTURES.items()))
def test_bad_fixture_is_flagged(name, rules):
    findings = analyze_file(FIXTURES / name, root=REPO)
    got = {f.rule for f in findings}
    assert rules <= got, (
        f"{name} must trip {sorted(rules)}, got {sorted(got)}:\n"
        + "\n".join(f.render() for f in findings))


@pytest.mark.parametrize("name", sorted(BAD_FIXTURES))
def test_good_twin_is_clean(name):
    good = name.replace("bad_", "good_")
    findings = analyze_file(FIXTURES / good, root=REPO)
    assert findings == [], (
        f"{good} must be clean:\n" + "\n".join(f.render() for f in findings))


LOOPED_COLUMN_BUILDERS = {
    "pid-column": "        for i, pid in enumerate(self._pid_col.tolist()):\n"
                  "            col[pid] = vids[i]\n",
    "posting-keys": "        for key in self._postings.tolist():\n"
                    "            col[key & 0xFFFFFFFF] = key >> 32\n",
    "comprehension": "        col[self._pid_col] = "
                     "[int(k) >> 32 for k in self._postings]\n",
}


@pytest.mark.parametrize("shape", [None, *sorted(LOOPED_COLUMN_BUILDERS)])
def test_dense_vid_column_builder_stands_under_the_postings_contract(shape):
    """``LabelPostings.dense_vids`` (what by/without group ids gather from)
    is in the rule's scope: as checked in it is one scatter and clean; the
    same column built one posting at a time is a finding IN that function."""
    from filodb_tpu.analysis.indexcheck import IndexChecker
    rel = "filodb_tpu/core/index_columnar.py"
    src = (REPO / rel).read_text()
    scatter = ("        col[self._pid_col] = np.repeat("
               "self._term_vids.astype(np.int32),\n"
               "                                       "
               "np.diff(self._term_offs))\n")
    assert src.count(scatter) == 1
    if shape is not None:
        src = src.replace(scatter, "        vids = np.repeat("
                          "self._term_vids, np.diff(self._term_offs))\n"
                          + LOOPED_COLUMN_BUILDERS[shape])
    found = IndexChecker().check_module(rel, ast.parse(src))
    if shape is None:
        assert found == [], [f.render() for f in found]
    else:
        assert [(f.rule, f.symbol) for f in found] == [
            ("index-pure-python-postings", "LabelPostings.dense_vids")], found


def test_tracer_record_sites_count_as_span_sites():
    """An interval recorded after the fact names its span like an opened
    one: ``tracer.record("literal", ...)`` is flagged, and a declared span
    that only ``tracer.record(SPAN_X, ...)`` uses is not dead surface."""
    bad = {f.detail for f in analyze_file(FIXTURES / "bad_trace_span.py",
                                          root=REPO)}
    assert "literal:fixture.late" in bad
    good = analyze_file(FIXTURES / "good_trace_span.py", root=REPO)
    assert not [f for f in good if "fixture.late" in f.detail], good


def _wire_findings(codec: str, classifier: str | None = None):
    spec = {
        "wire_module": codec,
        "classifier_module": classifier or codec,
        "error_base_modules": [],
        "codec_pairs": [("serialize_result", "deserialize_result"),
                        ("pack_multipart", "unpack_multipart")],
        "depth_pair": ("_enc_plan", "_dec_plan"),
        "error_root": "QueryError",
    }
    w = WireChecker(spec=spec)
    for rel in {codec, spec["classifier_module"]}:
        p = REPO / rel
        if p.exists():
            w.check_module(rel, ast.parse(p.read_text()))
    return w.finalize()


def test_bad_wire_fixture_is_flagged():
    rel = "tests/fixtures/filolint/bad_wire.py"
    findings = _wire_findings(rel)
    by_rule = {f.rule: f for f in findings}
    details = {f.detail for f in findings}
    assert "wire-tag-parity" in by_rule
    assert "undecoded:b'X'" in details          # result codec drift
    assert "undecoded:b'B'" in details          # multipart drift (B vs P)
    assert "unencoded:b'P'" in details
    assert any(f.rule == "wire-nesting-bound" and f.detail == "literal-bound"
               for f in findings)
    assert any(f.rule == "wire-error-classified"
               and f.detail == "shadowed:PeerGone" for f in findings)


def test_bad_wire_unclassified_when_no_dispatch_table():
    # classifier module with no try/except at all: every typed error is
    # unclassified
    rel = "tests/fixtures/filolint/bad_wire.py"
    findings = _wire_findings(rel,
                              classifier="tests/fixtures/filolint/good_jit_closure.py")
    unclassified = {f.detail for f in findings
                    if f.rule == "wire-error-classified"}
    assert "unclassified:PeerGone" in unclassified
    assert "unclassified:QueryError" in unclassified


def test_good_wire_fixture_is_clean():
    findings = _wire_findings("tests/fixtures/filolint/good_wire.py")
    assert findings == [], "\n".join(f.render() for f in findings)


def _op_findings(module_rel: str):
    spec = {
        "wire_module": "<none>",
        "classifier_module": "<none>",
        "error_base_modules": [],
        "codec_pairs": [],
        "depth_pair": ("_enc_plan", "_dec_plan"),
        "error_root": "QueryError",
        "op_specs": [{"module": module_rel, "prefix": "OP_",
                      "server_fn": "_serve", "client_class": "Client"}],
    }
    w = WireChecker(spec=spec)
    w.check_module(module_rel, ast.parse((REPO / module_rel).read_text()))
    return w.finalize()


def test_bad_wire_ops_fixture_is_flagged():
    findings = _op_findings("tests/fixtures/filolint/bad_wire_ops.py")
    details = {f.detail for f in findings}
    assert "op-unserved:OP_EVICT" in details     # client sends, server drops
    assert "op-unsent:OP_STATS" in details       # dead protocol arm
    assert "op-collision:OP_PING" in details or "op-collision:OP_DUP" in details
    assert all(f.rule == "wire-tag-parity" for f in findings)


def test_good_wire_ops_fixture_is_clean():
    findings = _op_findings("tests/fixtures/filolint/good_wire_ops.py")
    assert findings == [], "\n".join(f.render() for f in findings)


def _store_op_findings(module_rel: str):
    """Op-parity run shaped like the PRODUCTION diststore spec (server
    ``_serve`` + client class ``RemoteStore``)."""
    spec = {
        "wire_module": "<none>",
        "classifier_module": "<none>",
        "error_base_modules": [],
        "codec_pairs": [],
        "depth_pair": ("_enc_plan", "_dec_plan"),
        "error_root": "QueryError",
        "op_specs": [{"module": module_rel, "prefix": "OP_",
                      "server_fn": "_serve", "client_class": "RemoteStore"}],
    }
    w = WireChecker(spec=spec)
    w.check_module(module_rel, ast.parse((REPO / module_rel).read_text()))
    return w.finalize()


def test_bad_store_ops_fixture_is_flagged():
    findings = _store_op_findings("tests/fixtures/filolint/bad_store_ops.py")
    details = {f.detail for f in findings}
    # streaming op sent but never dispatched; checkpoint op dispatched but
    # never sent; two ops share one value
    assert "op-unserved:OP_APPEND_CRC" in details
    assert "op-unsent:OP_CHECKPOINT" in details
    assert any(d.startswith("op-collision:") for d in details)
    assert all(f.rule == "wire-tag-parity" for f in findings)


def test_good_store_ops_fixture_is_clean():
    findings = _store_op_findings("tests/fixtures/filolint/good_store_ops.py")
    assert findings == [], "\n".join(f.render() for f in findings)


def _cluster_op_findings(module_rel: str):
    """Op-parity run shaped like the PRODUCTION cluster spec (server
    ``serve_cluster`` + client class ``ClusterLink``)."""
    spec = {
        "wire_module": "<none>",
        "classifier_module": "<none>",
        "error_base_modules": [],
        "codec_pairs": [],
        "depth_pair": ("_enc_plan", "_dec_plan"),
        "error_root": "QueryError",
        "op_specs": [{"module": module_rel, "prefix": "OP_",
                      "server_fn": "serve_cluster",
                      "client_class": "ClusterLink"}],
    }
    w = WireChecker(spec=spec)
    w.check_module(module_rel, ast.parse((REPO / module_rel).read_text()))
    return w.finalize()


def test_bad_cluster_ops_fixture_is_flagged():
    findings = _cluster_op_findings(
        "tests/fixtures/filolint/bad_cluster_ops.py")
    details = {f.detail for f in findings}
    # REJOIN sync sent but never dispatched; announce dispatched but never
    # sent; the claim op collides with the read op's value
    assert "op-unserved:OP_SYNC" in details
    assert "op-unsent:OP_EPOCH_SET" in details
    assert any(d.startswith("op-collision:") for d in details)
    assert all(f.rule == "wire-tag-parity" for f in findings)


def test_good_cluster_ops_fixture_is_clean():
    findings = _cluster_op_findings(
        "tests/fixtures/filolint/good_cluster_ops.py")
    assert findings == [], "\n".join(f.render() for f in findings)


def _trace_parity_findings(module_rel: str):
    spec = {
        "wire_module": "<none>",
        "classifier_module": "<none>",
        "error_base_modules": [],
        "codec_pairs": [],
        "depth_pair": ("_enc_plan", "_dec_plan"),
        "error_root": "QueryError",
        "trace_specs": [
            {"symbol": "pack_trace_hdr",
             "sides": [[module_rel, "Client"]]},
            {"symbol": "unpack_trace_hdr",
             "sides": [[module_rel, "_serve"]]},
        ],
    }
    w = WireChecker(spec=spec)
    w.check_module(module_rel, ast.parse((REPO / module_rel).read_text()))
    return w.finalize()


def test_bad_trace_wire_fixture_is_flagged():
    findings = _trace_parity_findings(
        "tests/fixtures/filolint/bad_trace_wire.py")
    details = {f.detail for f in findings}
    assert "one-sided:unpack_trace_hdr" in details   # server never strips
    assert all(f.rule == "wire-trace-parity" for f in findings)


def test_good_trace_wire_fixture_is_clean():
    findings = _trace_parity_findings(
        "tests/fixtures/filolint/good_trace_wire.py")
    assert findings == [], "\n".join(f.render() for f in findings)


def test_production_trace_carriers_are_two_sided():
    """The REAL trace carriers: the /exec header pair and the broker /
    replication payload-block pairs both reference their carrier on every
    side today (the tier-1 shape of the PR-7 wire-header satellite)."""
    from filodb_tpu.analysis.wirecheck import WIRE_SPEC
    symbols = {s["symbol"] for s in WIRE_SPEC["trace_specs"]}
    assert {"TRACE_HEADER", "pack_trace_hdr", "unpack_trace_hdr"} <= symbols
    w = WireChecker()
    for spec in WIRE_SPEC["trace_specs"]:
        for module, _scope in spec["sides"]:
            if module not in w._modules:
                w.check_module(module,
                               ast.parse((REPO / module).read_text()))
    findings = [f for f in w.finalize() if f.rule == "wire-trace-parity"]
    assert findings == [], "\n".join(f.render() for f in findings)


def test_diststore_op_tags_are_exhaustive():
    """The production StoreServer protocol: every OP_* constant in
    core/diststore.py — including the PR-10 streaming (OP_APPEND_CRC) and
    checkpoint (OP_CHECKPOINT) ops — is dispatched by StoreServer._serve
    AND sent by the RemoteStore client, with distinct values."""
    import ast as _ast
    from filodb_tpu.analysis.wirecheck import WIRE_SPEC
    rel = "filodb_tpu/core/diststore.py"
    assert any(s["module"] == rel for s in WIRE_SPEC["op_specs"])
    tree = _ast.parse((REPO / rel).read_text())
    names = {t.id for node in tree.body if isinstance(node, _ast.Assign)
             for t in (node.targets[0].elts
                       if isinstance(node.targets[0], _ast.Tuple)
                       else node.targets)
             if isinstance(t, _ast.Name) and t.id.startswith("OP_")}
    assert {"OP_APPEND_CRC", "OP_CHECKPOINT"} <= names
    w = WireChecker()
    w.check_module(rel, tree)
    findings = w.finalize()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_broker_op_tags_are_exhaustive():
    """The production broker protocol itself: every OP_* constant is
    dispatched by BrokerServer._serve and sent by BrokerBus (the PR-4
    PUBLISH_BATCH satellite — a new op wired on one side only is a live
    protocol desync, not a unit-test failure)."""
    from filodb_tpu.analysis.wirecheck import WIRE_SPEC
    rel = "filodb_tpu/ingest/broker.py"
    assert any(s["module"] == rel for s in WIRE_SPEC["op_specs"])
    w = WireChecker()
    w.check_module(rel, ast.parse((REPO / rel).read_text()))
    assert w.finalize() == []


def test_cluster_op_tags_are_exhaustive():
    """The production cluster op family (PR 12): every OP_* constant in
    cluster/gossip.py — gossip, the epoch read/claim/announce triple, and
    the REJOIN sync — is dispatched by serve_cluster AND sent by
    ClusterLink, with distinct values (and clear of OP_REPLICATE's 16)."""
    import ast as _ast
    from filodb_tpu.analysis.wirecheck import WIRE_SPEC
    rel = "filodb_tpu/cluster/gossip.py"
    assert any(s["module"] == rel for s in WIRE_SPEC["op_specs"])
    tree = _ast.parse((REPO / rel).read_text())
    w = WireChecker()
    w.check_module(rel, tree)
    findings = w.finalize()
    assert findings == [], "\n".join(f.render() for f in findings)
    from filodb_tpu.cluster.gossip import CLUSTER_OPS
    from filodb_tpu.ingest.broker import (OP_END, OP_FETCH, OP_PUBLISH,
                                          OP_PUBLISH_BATCH)
    from filodb_tpu.ingest.replication import OP_REPLICATE
    taken = {OP_PUBLISH, OP_FETCH, OP_END, OP_PUBLISH_BATCH, OP_REPLICATE}
    assert not (CLUSTER_OPS & taken), (
        "cluster ops collide with broker/replication op values")


def test_real_wire_module_tags_are_exhaustive():
    """The production codec pair itself (not just the repo-wide zero-findings
    gate): both directions enumerate the same envelope tags today."""
    from filodb_tpu.analysis.wirecheck import _byte_tags, _functions
    tree = ast.parse((REPO / "filodb_tpu/query/wire.py").read_text())
    fns = _functions(tree)
    enc = set(_byte_tags(fns["serialize_result"]))
    dec = set(_byte_tags(fns["deserialize_result"]))
    assert enc == dec and {b"A", b"T", b"S", b"C", b"M"} <= enc


# -- suppression / baseline mechanics ---------------------------------------

def test_inline_suppression(tmp_path):
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self.lock = threading.RLock()\n"
        "    def _f_locked(self):\n"
        "        pass\n"
        "    def g(self):\n"
        "        self._f_locked()  # filolint: ignore[lock-unheld-call]\n"
    )
    p = tmp_path / "supp.py"
    p.write_text(src)
    assert analyze_file(p, root=tmp_path) == []
    # and without the comment it DOES flag
    p.write_text(src.replace("  # filolint: ignore[lock-unheld-call]", ""))
    assert [f.rule for f in analyze_file(p, root=tmp_path)] \
        == ["lock-unheld-call"]


def test_skip_file_suppression():
    supp = load_suppressions("# filolint: skip-file\nx = 1\n")
    f = Finding("lock-unheld-call", "x.py", 2, "m", "d", "msg")
    assert is_suppressed(f, supp)


def test_baseline_matches_by_fingerprint_not_line():
    f = Finding("lock-unheld-call", "pkg/m.py", 10, "C.m", "call:_x_locked",
                "msg")
    b = Baseline([{"rule": "lock-unheld-call", "file": "pkg/m.py",
                   "symbol": "C.m", "detail": "call:_x_locked",
                   "reason": "caller holds by contract"}])
    assert b.covers(f)
    moved = Finding("lock-unheld-call", "pkg/m.py", 99, "C.m",
                    "call:_x_locked", "msg")
    assert b.covers(moved)      # line drift doesn't invalidate the entry
    other = Finding("lock-unheld-call", "pkg/m.py", 10, "C.n",
                    "call:_x_locked", "msg")
    assert not b.covers(other)


# -- interprocedural engine mechanics -----------------------------------------

def test_helper_held_lock_closes_pr3_blind_spot():
    """The acceptance fixture: a private helper whose every in-class call
    site holds the owner lock. PR 3's lexical pass flagged the helper's
    *_locked call (holder-ness was per-function); the v2 inherited-holder
    fixpoint proves the lock is always held — and the bad twin (one
    non-holder call site) is still flagged."""
    good = analyze_file(FIXTURES / "good_lock_helper.py", root=REPO)
    assert good == [], "\n".join(f.render() for f in good)
    bad = analyze_file(FIXTURES / "bad_lock_helper.py", root=REPO)
    assert any(f.rule == "lock-unheld-call" and f.symbol == "Shard._bump"
               for f in bad)


def test_may_raise_propagates_through_helpers():
    """except-overbroad-typed depends on interprocedural may-raise: the
    typed raise lives two calls below the broad handler."""
    import textwrap
    from filodb_tpu.analysis.callgraph import PackageIndex
    src = textwrap.dedent("""
        class QueryError(Exception):
            pass
        def a():
            raise QueryError("x")
        def b():
            return a()
        def c():
            try:
                return b()
            except QueryError:
                return None
        def d():
            return c()
    """)
    idx = PackageIndex({"m.py": ast.parse(src)})
    mr = idx.may_raise(typed_only={"QueryError"})
    assert "QueryError" in mr["m.py::a"]
    assert "QueryError" in mr["m.py::b"]          # propagated up
    assert "QueryError" not in mr["m.py::c"]      # caught at the call site
    assert "QueryError" not in mr["m.py::d"]


def test_cfg_release_analysis_sees_exceptional_paths():
    from filodb_tpu.analysis import analyze_file as _af
    bad = _af(FIXTURES / "bad_resource_release.py", root=REPO)
    assert [f.rule for f in bad] == ["resource-no-release"]
    good = _af(FIXTURES / "good_resource_release.py", root=REPO)
    assert good == []


def test_overbroad_typed_respects_nested_handlers(tmp_path):
    """A defensive INNER `except QueryError` fully consumes the typed raise;
    the outer broad handler must stay clean (nested-frame filtering)."""
    src = (
        "class QueryError(Exception):\n"
        "    pass\n"
        "def helper():\n"
        "    raise QueryError('x')\n"
        "def outer(log):\n"
        "    try:\n"
        "        try:\n"
        "            return helper()\n"
        "        except QueryError:\n"
        "            return None\n"
        "    except Exception:\n"
        "        log('unexpected')\n"
        "        return None\n"
    )
    p = tmp_path / "nested.py"
    p.write_text(src)
    findings = analyze_file(p, root=tmp_path)
    assert not any(f.rule == "except-overbroad-typed" for f in findings), \
        "\n".join(f.render() for f in findings)
    # and WITHOUT the inner typed handler it does flag
    p.write_text(src.replace("        except QueryError:\n"
                             "            return None\n",
                             "        finally:\n"
                             "            pass\n"))
    findings = analyze_file(p, root=tmp_path)
    assert any(f.rule == "except-overbroad-typed" for f in findings)


def test_escaped_method_reference_defeats_holder_inheritance(tmp_path):
    """A private helper passed as a Thread target can run WITHOUT the lock
    even if its only direct call site holds it — the reference escape must
    block holder inheritance and keep PR 3's finding."""
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self.lock = threading.RLock()\n"
        "    def _bump_locked(self):\n"
        "        pass\n"
        "    def _bump(self):\n"
        "        self._bump_locked()\n"
        "    def kick(self):\n"
        "        with self.lock:\n"
        "            self._bump()\n"
        "        threading.Thread(target=self._bump, daemon=True).start()\n"
    )
    p = tmp_path / "escape.py"
    p.write_text(src)
    findings = analyze_file(p, root=tmp_path)
    assert any(f.rule == "lock-unheld-call" and f.symbol == "C._bump"
               for f in findings), "\n".join(f.render() for f in findings)


def test_may_raise_survives_log_and_reraise():
    """`except QueryError: raise` observes but does not terminate — the
    typed class must keep propagating so a downstream broad swallow is
    still flagged."""
    import textwrap
    from filodb_tpu.analysis.callgraph import PackageIndex
    src = textwrap.dedent("""
        class QueryError(Exception):
            pass
        def a():
            raise QueryError("x")
        def b(log):
            try:
                return a()
            except QueryError:
                log("typed failure")
                raise
    """)
    idx = PackageIndex({"m.py": ast.parse(src)})
    mr = idx.may_raise(typed_only={"QueryError"})
    assert "QueryError" in mr["m.py::b"]


def test_release_leak_through_nonmatching_handler(tmp_path):
    """An exception of a type the handler does NOT catch still escapes —
    the CFG must route it past non-terminal handler frames to EXIT."""
    bad = ("def f(p, use):\n"
           "    fh = open(p)\n"
           "    try:\n"
           "        use(fh)\n"
           "    except ValueError:\n"
           "        pass\n"
           "    fh.close()\n")
    p = tmp_path / "leak.py"
    p.write_text(bad)
    findings = analyze_file(p, root=tmp_path)
    assert any(f.rule == "resource-no-release" for f in findings), \
        "\n".join(f.render() for f in findings)
    # adding a finally makes every path (matched, unmatched, normal) release
    p.write_text(bad.replace("        pass\n    fh.close()\n",
                             "        pass\n    finally:\n"
                             "        fh.close()\n"))
    assert analyze_file(p, root=tmp_path) == []


def test_changed_only_rebases_paths_below_git_toplevel(tmp_path):
    """Porcelain paths are toplevel-relative; a vendored analysis root must
    still see its changed files instead of silently analyzing nothing."""
    import subprocess
    from filodb_tpu.analysis.__main__ import _changed_files
    sub = tmp_path / "vendor" / "repo"
    (sub / "filodb_tpu").mkdir(parents=True)
    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
    f = sub / "filodb_tpu" / "x.py"
    f.write_text("x = 1\n")
    subprocess.run(["git", "add", "-A"], cwd=tmp_path, check=True)
    assert _changed_files(sub) == ["filodb_tpu/x.py"]


def test_nested_def_trys_analyzed_once_with_own_sink_status(tmp_path):
    """A try inside a closure belongs to the closure's unit only: no
    duplicate findings from the enclosing method's walk, and a
    thread-target closure keeps its sink exemption."""
    src = (
        "import threading\n"
        "class QueryError(Exception):\n"
        "    pass\n"
        "def helper():\n"
        "    raise QueryError('x')\n"
        "class C:\n"
        "    def start(self, log):\n"
        "        def worker():\n"
        "            while True:\n"
        "                try:\n"
        "                    helper()\n"
        "                except Exception:\n"
        "                    log('fault; loop survives')\n"
        "        threading.Thread(target=worker, daemon=True).start()\n"
    )
    p = tmp_path / "closure.py"
    p.write_text(src)
    findings = analyze_file(p, root=tmp_path)
    overbroad = [f for f in findings if f.rule == "except-overbroad-typed"]
    assert overbroad == [], "\n".join(f.render() for f in findings)
    # and a swallow in a closure is reported exactly once (closure's unit)
    src2 = ("def outer(x):\n"
            "    def worker():\n"
            "        try:\n"
            "            return x()\n"
            "        except Exception:\n"
            "            pass\n"
            "    return worker\n")
    p.write_text(src2)
    swallows = [f for f in analyze_file(p, root=tmp_path)
                if f.rule == "except-swallow"]
    assert len(swallows) == 1 and swallows[0].symbol == "outer.worker"


def test_close_after_try_finally_is_clean(tmp_path):
    """The normal path through a try/finally continues to the code AFTER
    the try — no phantom function-exit edge may bypass a later release."""
    src = ("def f(p, use, log):\n"
           "    fh = open(p)\n"
           "    try:\n"
           "        use(fh)\n"
           "    finally:\n"
           "        log('done')\n"
           "    fh.close()\n")
    p = tmp_path / "after.py"
    p.write_text(src)
    findings = analyze_file(p, root=tmp_path)
    # close-after-the-try IS leaky on the exceptional path (use may raise;
    # the trailing close never runs) — that finding must stay...
    assert any(f.rule == "resource-no-release" for f in findings)
    # ...but moving the close INTO the finally covers every path, and the
    # normal-flow finally copy must not grow a phantom EXIT edge
    src_ok = src.replace("        log('done')\n    fh.close()\n",
                         "        log('done')\n        fh.close()\n")
    p.write_text(src_ok)
    assert analyze_file(p, root=tmp_path) == []


def test_bad_config_fixture_flags_dead_toplevel_key():
    findings = analyze_file(FIXTURES / "bad_config_key.py", root=REPO)
    details = {f.detail for f in findings
               if f.rule == "surface-config-unused"}
    assert {"key:ingest.retired_knob", "key:retired_flag"} <= details


def test_update_baseline_narrow_scope_preserves_out_of_scope_entries(tmp_path):
    """--update-baseline on a narrowed path set must not delete baseline
    promises for files it never re-analyzed."""
    import json as _json
    from filodb_tpu.analysis.__main__ import main
    swallow = ("def f(x):\n"
               "    try:\n"
               "        return x()\n"
               "    except Exception:\n"
               "        pass\n")
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text(swallow)
    b.write_text(swallow)
    bl = tmp_path / "bl.json"
    # baseline BOTH files' findings via a full-scope pass
    assert main(["--root", str(tmp_path), str(a), str(b), "--baseline",
                 str(bl), "--update-baseline", "--reason", "fixture"]) == 0
    entries = _json.loads(bl.read_text())["entries"]
    assert {e["file"] for e in entries} == {"a.py", "b.py"}
    # narrow re-baseline of a.py only: b.py's promise must survive
    assert main(["--root", str(tmp_path), str(a), "--baseline", str(bl),
                 "--update-baseline", "--reason", "fixture"]) == 0
    entries = _json.loads(bl.read_text())["entries"]
    assert {e["file"] for e in entries} == {"a.py", "b.py"}


# -- tooling: output formats, baseline discipline -----------------------------

def test_baseline_write_refuses_missing_reason(tmp_path):
    f = Finding("except-swallow", "m.py", 3, "f", "swallow:1", "msg")
    with pytest.raises(ValueError):
        Baseline.write(tmp_path / "b.json", [f])
    Baseline.write(tmp_path / "b.json", [f], reason="intentional: probe")
    b = Baseline.load(tmp_path / "b.json")
    assert b.covers(f) and b.entries[0]["reason"] == "intentional: probe"


def test_update_baseline_cli_refuses_without_reason(tmp_path):
    """--update-baseline with new findings and no --reason exits 2 and does
    not write."""
    from filodb_tpu.analysis.__main__ import main
    bad = tmp_path / "bad_swallow.py"
    bad.write_text("def f(x):\n"
                   "    try:\n"
                   "        return x()\n"
                   "    except Exception:\n"
                   "        pass\n")
    bl = tmp_path / "bl.json"
    rc = main(["--root", str(tmp_path), str(bad), "--baseline", str(bl),
               "--update-baseline", "--quiet"])
    assert rc == 2 and not bl.exists()
    rc = main(["--root", str(tmp_path), str(bad), "--baseline", str(bl),
               "--update-baseline", "--reason", "fixture: deliberate"])
    assert rc == 0 and bl.exists()
    # baselined now: a plain run is clean against the updated baseline
    assert main(["--root", str(tmp_path), str(bad), "--baseline", str(bl),
                 "--quiet"]) == 0


def test_output_formats_are_machine_readable(capsys):
    import json as _json
    from filodb_tpu.analysis.__main__ import main
    assert main(["--root", str(REPO), "--format", "json"]) == 0
    report = _json.loads(capsys.readouterr().out)
    assert report["counts"]["new"] == 0 and report["files_analyzed"] > 50
    assert main(["--root", str(REPO), "--format", "sarif"]) == 0
    sarif = _json.loads(capsys.readouterr().out)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "filolint"
    assert run["results"] == []          # zero NEW findings repo-wide
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"resource-no-release", "except-overbroad-typed",
            "surface-config-undeclared"} <= rule_ids


# -- declared surfaces: spec <-> docs parity ----------------------------------

def test_readme_config_table_matches_spec():
    from filodb_tpu.config import CONFIG_SPEC, config_markdown_table
    readme = (REPO / "README.md").read_text()
    assert config_markdown_table() in readme, (
        "README Configuration table drifted from config.py CONFIG_SPEC — "
        "regenerate it with filodb_tpu.config.config_markdown_table()")
    assert len(CONFIG_SPEC) >= 40


def test_readme_metrics_table_matches_spec():
    from filodb_tpu.utils.metrics import METRICS_SPEC, metrics_markdown_table
    readme = (REPO / "README.md").read_text()
    assert metrics_markdown_table() in readme, (
        "README Metrics table drifted from utils/metrics.py METRICS_SPEC — "
        "regenerate it with filodb_tpu.utils.metrics.metrics_markdown_table()")
    assert "filodb_swallowed_errors" in METRICS_SPEC


def test_architecture_span_table_matches_spec():
    from filodb_tpu.utils.tracing import TRACE_SPEC, trace_markdown_table
    arch = (REPO / "ARCHITECTURE.md").read_text()
    assert trace_markdown_table() in arch, (
        "ARCHITECTURE span-taxonomy table drifted from utils/tracing.py "
        "TRACE_SPEC — regenerate it with "
        "filodb_tpu.utils.tracing.trace_markdown_table()")
    assert len(TRACE_SPEC) >= 15


def test_defaults_derive_from_config_spec():
    """One source of truth: the DEFAULTS tree is exactly the nested form of
    CONFIG_SPEC's defaults, and Config resolves every declared key."""
    from filodb_tpu.config import CONFIG_SPEC, Config
    cfg = Config()
    for key, (_typ, default, _doc) in CONFIG_SPEC.items():
        assert cfg[key] == default, key


# -- 2. repo enforcement ------------------------------------------------------

def test_repo_has_zero_unsuppressed_findings():
    report = run_analysis(REPO)
    assert report.files_analyzed > 50
    assert report.new == [], (
        "filolint found NEW violations — fix them, suppress inline with a "
        "reason, or baseline them:\n"
        + "\n".join(f.render() for f in report.new))


def test_cli_exit_status():
    from filodb_tpu.analysis.__main__ import main
    assert main(["--root", str(REPO), "--quiet"]) == 0


def test_shared_corpus_matches_and_beats_per_family():
    """PR 18 satellite: all rule families run over ONE parsed corpus with
    one PackageIndex and memoized CFGs. The legacy per-family mode (each
    family re-parses and re-indexes) must produce fingerprint-identical
    findings — from measurably more work, or the sharing rotted away.
    Counted, not timed: under six xdist workers a wall clock says how
    loaded the sandbox is, not what the runner did."""
    from filodb_tpu.analysis.runner import _default_checkers
    shared = run_analysis(REPO, shared_corpus=True)
    legacy = run_analysis(REPO, shared_corpus=False)
    fps = sorted(f.fingerprint for f in shared.all_findings)
    assert fps == sorted(f.fingerprint for f in legacy.all_findings)
    n, families = shared.files_analyzed, len(_default_checkers(None, True))
    assert families > 1
    assert shared.corpus_stats["index_builds"] == 1
    assert shared.corpus_stats["files_parsed"] == n
    assert legacy.corpus_stats["files_parsed"] == n * families
    assert legacy.corpus_stats["index_builds"] > 1


def test_sarif_artifact_is_current():
    """The committed SARIF artifact (CI code-scanning upload) declares
    every rule — including the PR 18 epoch family and the stale-ignore
    meta-rule — and carries zero results (the repo is clean)."""
    import json
    from filodb_tpu.analysis.runner import ALL_RULES
    art = json.loads((REPO / "filolint.sarif").read_text())
    driver = art["runs"][0]["tool"]["driver"]
    assert tuple(r["id"] for r in driver["rules"]) == ALL_RULES
    assert art["runs"][0]["results"] == []
    for rule in ("epoch-undeclared-visibility", "epoch-bump-uncovered",
                 "epoch-bump-unlocked", "epoch-bump-overclaim",
                 "epoch-capture-after-execute", "epoch-validate-refetched",
                 "filolint-stale-ignore",
                 # PR 20 liveness family
                 "live-block-under-lock", "live-unbounded-io",
                 "live-unbounded-retry", "live-wait-no-timeout"):
        assert rule in ALL_RULES, rule


def test_stale_ignore_only_suppressed_by_naming_itself(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("def f():\n"
                 "    return 1  # filolint: ignore[jit-host-sync]\n")
    assert any(f.rule == "filolint-stale-ignore"
               for f in analyze_file(p, root=tmp_path))
    # a blanket ignore[*] cannot swallow the meta-finding about itself...
    p.write_text("def f():\n"
                 "    return 1  # filolint: ignore[jit-host-sync, *]\n")
    assert any(f.rule == "filolint-stale-ignore"
               for f in analyze_file(p, root=tmp_path))
    # ...but explicitly accepting the meta-rule by name works
    p.write_text("def f():\n"
                 "    return 1  "
                 "# filolint: ignore[jit-host-sync, filolint-stale-ignore]\n")
    assert analyze_file(p, root=tmp_path) == []


def test_stale_ignore_skipped_in_scoped_runs():
    """cli.py's except-swallow suppression is live in a full run but its
    rule is interprocedural — a scoped run must not call it stale."""
    report = run_analysis(REPO, paths=["filodb_tpu/cli.py"])
    assert not any(f.rule == "filolint-stale-ignore"
                   for f in report.all_findings)


def test_changed_only_escalates_on_analysis_changes(tmp_path, capsys):
    """A change under filodb_tpu/analysis/ (or to the fixture twins)
    invalidates every scoped judgement — --changed-only must escalate to
    a full run instead of linting new rules against a partial corpus."""
    import subprocess
    from filodb_tpu.analysis.__main__ import main
    (tmp_path / "filodb_tpu" / "analysis").mkdir(parents=True)
    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
    (tmp_path / "filodb_tpu" / "analysis" / "newrule.py").write_text("x = 1\n")
    subprocess.run(["git", "add", "-A"], cwd=tmp_path, check=True)
    rc = main(["--root", str(tmp_path), "--changed-only", "--quiet"])
    assert rc == 0
    assert "escalating" in capsys.readouterr().err


def test_epoch_spec_module_is_changed_only_anchor():
    """The epoch rules judge every mutator against core/memstore.py's
    EPOCH_SPEC — a scoped run must always carry it."""
    from filodb_tpu.analysis.__main__ import ANCHOR_MODULES
    assert "filodb_tpu/core/memstore.py" in ANCHOR_MODULES


def test_latency_spec_module_is_changed_only_anchor():
    """The liveness rules judge lock-held spans, waits and retries against
    utils/diagnostics.py's LATENCY_SPEC — a scoped run must carry it."""
    from filodb_tpu.analysis.__main__ import ANCHOR_MODULES
    assert "filodb_tpu/utils/diagnostics.py" in ANCHOR_MODULES


def test_latency_spec_lock_classes_match_runtime_order():
    """LATENCY_SPEC's lock classes and the runtime LOCK_ORDER are two views
    of the same lock taxonomy — a class declared in one but not the other
    means a lock the watchdog times but the static rules ignore (or vice
    versa)."""
    from filodb_tpu.utils.diagnostics import LATENCY_SPEC
    assert set(LATENCY_SPEC["locks"].values()) == set(RUNTIME_LOCK_ORDER)
    # every declared sanction must carry a non-empty reason — the checker
    # enforces this on the AST; this keeps the runtime literal honest too
    for section in ("sites", "wait_ok", "retry_ok"):
        for name, site in LATENCY_SPEC.get(section, {}).items():
            assert site.get("fn"), (section, name)
            assert str(site.get("reason", "")).strip(), (section, name)


def test_include_tools_audit_never_affects_exit_status(capsys):
    from filodb_tpu.analysis.__main__ import _tools_audit, main
    rc = main(["--root", str(REPO), "--quiet", "--include-tools"])
    assert rc == 0              # warnings only, even when findings exist
    capsys.readouterr()
    # the audit reports tool findings as prefixed warning lines (stress/
    # and scripts/ are outside the enforced package, but their hangs
    # still wedge CI); findings in the spec anchor module belong to the
    # main run and must not be duplicated here
    for line in _tools_audit(REPO):
        assert line.startswith("filolint: tools-audit")
        assert "utils/diagnostics.py" not in line.split("]")[0]


# -- 3. runtime hook parity ---------------------------------------------------

def test_lock_order_declared_once():
    assert STATIC_LOCK_ORDER == RUNTIME_LOCK_ORDER


def test_runtime_lock_order_assert_fires():
    was = diagnostics.lock_debug
    diagnostics.enable_lock_debug(True)
    try:
        shard = diagnostics.TimedRLock("t-shard", order_class="shard",
                                       order_index=0)
        shard1 = diagnostics.TimedRLock("t-shard-1", order_class="shard",
                                        order_index=1)
        sink = diagnostics.TimedRLock("t-sink", order_class="sink")
        grp = diagnostics.TimedRLock("t-grp", order_class="group_flush")
        # declared order is fine, including reentrancy and ascending
        # same-class indexes (the engine's multi-shard ExitStack shape)
        with grp, sink, shard, shard, shard1:
            pass
        # out of order: shard then sink must raise BEFORE blocking
        with shard:
            with pytest.raises(diagnostics.DiagnosticsError):
                sink.acquire()
        # same class, DESCENDING index: the ABBA shape
        with shard1:
            with pytest.raises(diagnostics.DiagnosticsError):
                shard.acquire()
        # the failed acquisitions must not have left state behind
        with grp, sink, shard:
            pass
    finally:
        diagnostics.enable_lock_debug(was)


def test_memstore_locks_are_ordered():
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    ms = TimeSeriesMemStore()
    sh = ms.setup("lintcheck", "gauge", 0,
                  StoreConfig(max_series_per_shard=8, samples_per_series=16))
    assert sh.lock.order_class == "shard"
    assert sh._sink_lock.order_class == "sink"
    assert all(lk.order_class == "group_flush"
               for lk in sh._group_flush_locks)
