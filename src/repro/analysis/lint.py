"""zenlint driver: certify every registered scheme's lowered program.

``python -m repro.analysis.lint`` runs three layers and exits nonzero on
any finding:

  * AST lint (``--ast-only``): registry-contract rules over the source
    tree (ast_rules.AST1-AST3).
  * Registry coverage (``--registry-only``): the former
    ``make check-registry`` — every scheme has sane volume/rounds
    functions and a tier-1 parity test (folded in here).
  * HLO sweep (``--hlo-only``): for every executable scheme x {flat,
    hier} x n in {2, 8}, lower a saturating sync program once on the
    host-platform mesh and run the R1-R5 catalog (analysis/rules) over
    the optimized HLO, the StableHLO, and (for the run_schedule subject)
    the jaxpr.  Wire expectations come from the registry's
    ``wire_words_fn`` metadata; a scheme registered without lint
    metadata is itself a finding.

The sweep executes each program too (cheap at these sizes): overflow or
a wrong sum is reported as a DRIVER finding — a lint that certifies
bytes of a numerically wrong program would be theater.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from typing import Dict, List, Optional, Tuple

from repro.analysis import ast_rules, hlo_ir, rules
from repro.analysis.rules import Finding, Subject, WireExpectation

WORD = 4  # f32/i32 wire word, bytes

DEFAULT_NS = (2, 8)
DEFAULT_M = 4096
SCHED_BUCKETS = 3


def _ensure_host_devices() -> None:
    """Must run before the first jax import in this process."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def _payload(M: int, n: int, density: float):
    """Per-worker [n, M] grads: identical support on every worker (claims
    stay device-symmetric), distinct dyadic values (sums are exact)."""
    import numpy as np
    g = np.zeros((n, M), np.float32)
    stride = max(1, int(round(1.0 / density)))
    pos = np.arange(0, M, stride)
    for i in range(n):
        g[i, pos] = 1.0 + i / 8.0 + (pos % 7) / 64.0
    return g


def _stage_setup(spec, M: int, n_level: int, overrides=None):
    """(StageArgs, expected wire words) for one level of size n_level.

    ``overrides`` — ((StageArgs field, value), ...) from a
    ``SchemeSpec.lint_routes`` entry: a compute-route variant (e.g. zen's
    fused-commit megakernel) that must satisfy the SAME wire contract —
    the expectation is computed from the un-overridden kwargs, so a route
    that changes a transmitted word fails R2."""
    from repro.core import registry as sreg
    from repro.core import schemes
    kwargs = dict(spec.lint_caps_fn(M, n_level)) if spec.lint_caps_fn else {}
    args = sreg.StageArgs(**kwargs)
    if "layout" in spec.stage_args:
        layout = schemes.make_zen_layout(
            M, n_level, density_budget=min(1.0, 2 * spec.lint_density))
        args = dataclasses.replace(args, layout=layout)
    kw = sreg.stage_kwargs(spec, args)
    exp_words = (spec.wire_words_fn(M, n_level, kw)
                 if spec.wire_words_fn else None)
    if overrides:
        args = dataclasses.replace(args, **dict(overrides))
    return args, exp_words


def _meta_findings(spec, label: str) -> List[Finding]:
    """A scheme cannot enter the sweep without its wire contract."""
    missing = [f for f, v in (("wire_words_fn", spec.wire_words_fn),
                              ("expected_collectives",
                               spec.expected_collectives)) if not v]
    if not missing:
        return []
    return [Finding(
        "R2", f"scheme {spec.name!r} registered without zenlint metadata "
              f"({', '.join(missing)}) — register the wire contract "
              f"(core/costmodel.py) before it can be certified",
        case=label)]


def _run_and_lower(jfn, g, label: str):
    """Execute + lower once; returns (stats arrays, subject pieces,
    driver findings)."""
    import numpy as np
    findings: List[Finding] = []
    out, words, ov = jfn(g)
    if int(np.asarray(ov).sum()) != 0:
        findings.append(Finding(
            "DRIVER", f"lint payload overflowed a capacity "
                      f"(overflow={int(np.asarray(ov).sum())}) — "
                      f"lint_caps_fn does not saturate exactly",
            case=label))
    ga = np.asarray(g)
    want = ga.reshape(-1, ga.shape[-1]).sum(0)  # sum over all workers
    got = np.asarray(out)
    if not np.allclose(got, want, atol=1e-5):
        findings.append(Finding(
            "DRIVER", f"synced result != sum of workers (max err "
                      f"{float(abs(got - want).max()):.2e})", case=label))
    lowered = jfn.lower(g)
    stablehlo = lowered.as_text()
    hlo = lowered.compile().as_text()
    return np.asarray(words), stablehlo, hlo, findings


def build_flat_subject(
        scheme: str, n: int, M: int, route=None
) -> Tuple[Optional[Subject], List[Finding]]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import registry as sreg
    from repro.core import schemes

    label = f"{scheme} flat n={n}"
    overrides = None
    if route is not None:
        rlabel, overrides = route
        label = f"{label} [{rlabel}]"
    spec = sreg.get_scheme(scheme)
    findings = _meta_findings(spec, label)
    if findings:
        return None, findings
    args, exp_words = _stage_setup(spec, M, n, overrides=overrides)
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("data",))

    def local(v):
        out, st = schemes.stage_sync(scheme, v[0], axis="data", n=n,
                                     stage_args=args)
        return out, st.sent_words[None], st.overflow[None]

    mapped = jax.shard_map(local, mesh=mesh, in_specs=P("data"),
                           out_specs=(P(), P("data"), P("data")),
                           check_vma=False)
    g = jnp.asarray(_payload(M, n, spec.lint_density))
    words, stablehlo, hlo, findings = _run_and_lower(
        jax.jit(mapped), g, label)
    claimed = float(words.reshape(-1).max()) * WORD
    subject = Subject(
        label=label,
        module=hlo_ir.HloModule.parse(hlo),
        stablehlo_text=stablehlo,
        wire={n: WireExpectation(
            expected_bytes=exp_words * WORD, claimed_bytes=claimed,
            kinds=spec.expected_collectives,
            claim_exact=spec.lint_saturable)},
        exempt=spec.lint_exempt)
    return subject, findings


def build_hier_subject(
        scheme: str, n: int, M: int, node_size: int = 2
) -> Tuple[Optional[Subject], List[Finding]]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import registry as sreg
    from repro.core import schemes
    from repro.core import topology as tp

    label = f"hier({scheme}@intra,{scheme}@inter) n={n} node={node_size}"
    spec = sreg.get_scheme(scheme)
    findings = _meta_findings(spec, label)
    if findings:
        return None, findings
    topo = tp.build_topology(n, node_size)
    plan = tp.hier_plan(scheme, scheme)
    stage_kw, wire = {}, {}
    for li, lvl in enumerate(topo.levels):
        if lvl.size <= 1:
            continue
        if not spec.feasible(lvl.size, M):
            return None, []  # this scheme cannot run at this level size
        args, exp_words = _stage_setup(spec, M, lvl.size)
        stage_kw[li] = args
        wire[lvl.size] = exp_words  # group sizes distinct (2 vs n//2)
    n_intra, n_inter = topo.intra.size, topo.inter.size
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(n_inter, n_intra),
                (tp.DP_INTER, tp.DP_INTRA))

    def local(v):
        out, st = schemes.hier_sync(v[0, 0], topology=topo, plan=plan,
                                    stage_kw=stage_kw)
        lv = jnp.stack(list(st.by_level))
        return out, lv[None, None], st.overflow[None, None]

    spec2 = P(tp.DP_INTER, tp.DP_INTRA)
    mapped = jax.shard_map(local, mesh=mesh, in_specs=spec2,
                           out_specs=(P(), spec2, spec2), check_vma=False)
    g = jnp.asarray(_payload(M, n, spec.lint_density)
                    ).reshape(n_inter, n_intra, M)
    by_level, stablehlo, hlo, findings = _run_and_lower(
        jax.jit(mapped), g, label)
    # by_level: [n_inter, n_intra, n_levels] -> claimed words per level
    by_level = by_level.reshape(-1, len(topo.levels))
    expectations: Dict[int, WireExpectation] = {}
    for li, lvl in enumerate(topo.levels):
        if lvl.size not in wire:
            continue
        expectations[lvl.size] = WireExpectation(
            expected_bytes=wire[lvl.size] * WORD,
            claimed_bytes=float(by_level[:, li].max()) * WORD,
            kinds=spec.expected_collectives,
            claim_exact=spec.lint_saturable)
    subject = Subject(
        label=label,
        module=hlo_ir.HloModule.parse(hlo),
        stablehlo_text=stablehlo,
        wire=expectations,
        exempt=spec.lint_exempt)
    return subject, findings


def build_schedule_subject(
        n: int = 8, M: int = 2048, nb: int = SCHED_BUCKETS
) -> Tuple[Subject, List[Finding]]:
    """The run_schedule overlap pipeline as a lint subject (R4).

    A flat zen pipeline over ``nb`` buckets: encode is collective-free,
    so every optimization_barrier input must be independent of any
    collective — the double-buffering contract (train/schedule.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import buckets as bk
    from repro.core import schemes
    from repro.train import schedule

    label = f"run_schedule zen nb={nb} flat n={n}"
    density = 0.25
    layout = schemes.make_zen_layout(M, n, density_budget=2 * density)
    bucks = [bk.Bucket(bid=i, kind=bk.DENSE, scheme="zen",
                       slots=(bk.LeafSlot(f"w{i}", i, (M,), jnp.float32,
                                          0, M),),
                       nbytes=M * WORD)
             for i in range(nb)]
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("data",))

    def local(v):  # [1, nb, M]
        payloads = [v[0, i] for i in range(nb)]

        def encode(b, p):
            return (p, schemes.zen_encode(p, layout=layout))

        def commit(b, pe):
            p, enc = pe
            return schemes.zen_commit(enc, p, axis="data", layout=layout)

        outs, stats = schedule.run_schedule(bucks, payloads, encode, commit)
        words = sum(st.sent_words for st in stats)
        ov = sum(st.overflow for st in stats)
        return jnp.stack(outs), words[None], ov[None]

    mapped = jax.shard_map(local, mesh=mesh, in_specs=P("data"),
                           out_specs=(P(), P("data"), P("data")),
                           check_vma=False)
    base = _payload(M, n, density)
    g = jnp.asarray(np.stack([base * (1 + b / 16.0) for b in range(nb)],
                             axis=1))  # [n, nb, M]
    jfn = jax.jit(mapped)
    findings: List[Finding] = []
    out, words, ov = jfn(g)
    if int(np.asarray(ov).sum()) != 0:
        findings.append(Finding("DRIVER", "schedule payload overflowed",
                                case=label))
    want = np.asarray(g).sum(0)
    if not np.allclose(np.asarray(out), want, atol=1e-4):
        findings.append(Finding("DRIVER",
                                "scheduled sync != sum of workers",
                                case=label))
    lowered = jfn.lower(g)
    subject = Subject(
        label=label,
        module=hlo_ir.HloModule.parse(lowered.compile().as_text()),
        stablehlo_text=lowered.as_text(),
        jaxpr=jax.make_jaxpr(mapped)(g),
        expected_fences=nb - 1,
        fences_collective_free=True)
    return subject, findings


def run_hlo_sweep(schemes_filter: Optional[List[str]] = None,
                  ns: Tuple[int, ...] = DEFAULT_NS,
                  M: int = DEFAULT_M,
                  with_schedule: bool = True,
                  verbose: bool = True) -> List[Finding]:
    from repro.core import registry as sreg

    findings: List[Finding] = []
    names = sreg.registered_schemes(executable_only=True)
    if schemes_filter:
        unknown = sorted(set(schemes_filter) - set(names))
        if unknown:
            raise SystemExit(f"unknown scheme(s): {', '.join(unknown)} "
                             f"(executable: {', '.join(names)})")
        names = tuple(s for s in names if s in schemes_filter)
    for scheme in names:
        spec = sreg.get_scheme(scheme)
        for waived in spec.lint_exempt:
            print(f"  WAIVED {scheme}: rule {waived} "
                  f"(SchemeSpec.lint_exempt)")
        for n in ns:
            for build, kind in ((build_flat_subject, "flat"),
                                (build_hier_subject, "hier")):
                if kind == "flat" and not spec.feasible(n, M):
                    continue
                subject, extra = build(scheme, n, M)
                findings.extend(extra)
                if subject is None:
                    continue
                got = rules.run_rules(subject)
                findings.extend(got)
                if verbose:
                    status = ("ok" if not (got or extra)
                              else f"{len(got) + len(extra)} finding(s)")
                    print(f"  {subject.label}: {status}")
            # compute-route variants (SchemeSpec.lint_routes): same R1-R5
            # catalog, same wire contract — a fused route that changed a
            # single transmitted word fails here
            for route in spec.lint_routes:
                if not spec.feasible(n, M):
                    continue
                subject, extra = build_flat_subject(scheme, n, M,
                                                    route=route)
                findings.extend(extra)
                if subject is None:
                    continue
                got = rules.run_rules(subject)
                findings.extend(got)
                if verbose:
                    status = ("ok" if not (got or extra)
                              else f"{len(got) + len(extra)} finding(s)")
                    print(f"  {subject.label}: {status}")
    want_sched = (not schemes_filter
                  or "zen" in schemes_filter)  # zenlint: ignore[AST2]
    if with_schedule and want_sched:
        subject, extra = build_schedule_subject()
        got = rules.run_rules(subject)
        findings.extend(extra + got)
        if verbose:
            status = "ok" if not (got or extra) else \
                f"{len(got) + len(extra)} finding(s)"
            print(f"  {subject.label}: {status}")
    return findings


def registry_findings(tests_dir: str = "tests") -> List[Finding]:
    from repro.core import registry as sreg
    return [Finding("REG", e, case="registry coverage")
            for e in sreg.coverage_errors(tests_dir)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.analysis.lint",
        description="zenlint: certify every registered scheme's lowered "
                    "program against the R1-R5 invariant catalog, the "
                    "registry contract (AST), and registry coverage.")
    layer = ap.add_mutually_exclusive_group()
    layer.add_argument("--ast-only", action="store_true",
                       help="source-tree registry-contract lint only")
    layer.add_argument("--hlo-only", action="store_true",
                       help="HLO sweep (R1-R5) only")
    layer.add_argument("--registry-only", action="store_true",
                       help="registry-coverage check only (the former "
                            "`make check-registry`)")
    ap.add_argument("--schemes", default=None,
                    help="comma-separated scheme filter for the sweep")
    ap.add_argument("--ns", default=",".join(map(str, DEFAULT_NS)),
                    help="comma-separated worker counts (default 2,8)")
    ap.add_argument("--m", type=int, default=DEFAULT_M,
                    help=f"payload length (default {DEFAULT_M})")
    ap.add_argument("--tree", default="src/repro",
                    help="root for the AST layer")
    ap.add_argument("--tests-dir", default="tests",
                    help="tier-1 test dir for registry coverage")
    args = ap.parse_args(argv)

    do_ast = args.ast_only or not (args.hlo_only or args.registry_only)
    do_reg = args.registry_only or not (args.ast_only or args.hlo_only)
    do_hlo = args.hlo_only or not (args.ast_only or args.registry_only)

    findings: List[Finding] = []
    if do_ast:
        print(f"zenlint: AST rules over {args.tree}")
        findings.extend(ast_rules.run_tree(args.tree))
    if do_reg:
        print("zenlint: registry coverage")
        findings.extend(registry_findings(args.tests_dir))
    if do_hlo:
        _ensure_host_devices()
        ns = tuple(int(x) for x in args.ns.split(",") if x)
        flt = (args.schemes.split(",") if args.schemes else None)
        print(f"zenlint: HLO sweep (R1-R5), n in {ns}, M={args.m}")
        findings.extend(run_hlo_sweep(flt, ns, args.m))

    for f in findings:
        print(f"FINDING {f}")
    n_rules = len(rules.RULES)
    print(f"zenlint: {len(findings)} finding(s) "
          f"[{n_rules} HLO rules, 3 AST rules] — "
          f"{'FAIL' if findings else 'ok'}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
