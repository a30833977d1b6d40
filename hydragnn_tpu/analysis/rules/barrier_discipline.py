"""barrier-discipline: name minting, collective placement, and
rendezvous symmetry on multi-process coordination paths.

The reference framework's hard failures are DDP rendezvous hangs and
collective mismatches; our own PR-13 review found the local analogue —
barrier names minted from CALL-SITE counters, where one process
failing mid-job desyncs every later name and wedges every subsequent
save. The durable contract (docs/DURABILITY.md "Barrier identity"):
barrier/KV names derive from the WRITER'S ENQUEUE-TIME per-job
sequence (``CheckpointWriter._job_seq``, minted in ``save()`` on the
caller thread and carried with the job), never from whatever a call
site happens to count. This rule enforces three checks statically over
multi-process-reachable code — the registered coordination seeds plus
every function carrying the per-process-path marker (a direct
``wait_at_barrier`` / ``key_value_set`` / ``blocking_key_value_get``),
closed over call edges:

**Counter-minted names.** A barrier/KV name argument that interpolates
a value minted AT THE CALL SITE — ``_barrier_seq(...)``, bare
``next(...)``, ``time.time()``, ``os.getpid()``, ``id(...)`` — is
flagged at the mint site: after one asymmetric failure the counters
disagree across processes forever (process A waits at ``tag:7`` while
process B waits at ``tag:8`` — both time out, and so does every save
after them). ``_process_barrier(...)`` called WITHOUT ``seq=`` is the
same bug via the helper's internal fallback and is flagged at the call
site, anywhere in the tree. Values received as PARAMETERS are clean —
that is exactly the enqueue-time-sequence idiom. The sanctioned
fallback sites (the end-of-run barrier every process reaches the same
number of times) carry ``disable=barrier-discipline -- why`` in place.

**XLA collectives on coordination paths.** ``sync_global_devices`` /
``process_allgather`` / ``lax.psum``-family calls on a
coordination-only path queue device work behind the step stream from
a worker thread, and hang the moment one process takes the path and
another does not. Coordination paths use the coordination-service KV
store, full stop. (SPMD collectives on the main compute path — ``test()``'s
gather — are out of scope by construction: they are not reachable
from the coordination seeds.)

**Conditional rendezvous.** A barrier WAIT (``wait_at_barrier`` /
``_process_barrier`` / ``_processes_agree_finite``) lexically under an
``if`` testing ``process_index`` means one process can skip a
rendezvous its peers perform — they hang until timeout.
``process_count`` tests are uniform across processes and sanctioned;
asymmetric KV set/get under a ``process_index`` test is the designed
O(P) aggregation pattern (``_processes_agree_finite``) and is NOT
flagged — only the rendezvous itself must be unconditional.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from hydragnn_tpu.analysis.callgraph import (
    _COORD_OPS,
    coord_sites,
    module_env,
    own_statements,
    seed_scope,
)
from hydragnn_tpu.analysis.engine import Finding, LintContext, Rule

# The multi-process-reachable surfaces (docs/DURABILITY.md): the
# checkpoint worker and its save/publish path, the barrier/agreement
# helpers themselves, and the walltime broadcast. Functions carrying
# the per-process-path marker (direct coordination-service ops) join
# the scope automatically — a new coordination call site cannot dodge
# the rule by not being registered here.
COORD_SEEDS = (
    ("utils/checkpoint.py", "_process_barrier"),
    ("utils/checkpoint.py", "_processes_agree_finite"),
    ("utils/checkpoint.py", "_barrier_seq"),
    ("utils/checkpoint.py", "CheckpointWriter._worker_main"),
    ("utils/checkpoint.py", "CheckpointWriter.save"),
    ("utils/checkpoint.py", "_orbax_checkpointer"),
    ("utils/runtime.py", "check_remaining"),
)

# Call-site mints: interpolating any of these into a barrier/KV name
# desyncs processes after one asymmetric failure.
_MINT_TIME = {("time", "time"), ("time", "monotonic"), ("os", "getpid")}

_COLLECTIVE_ANY_BASE = ("sync_global_devices", "process_allgather")
_COLLECTIVE_LAX = (
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
)
_BARRIER_WAITS = (
    "wait_at_barrier", "_process_barrier", "_processes_agree_finite",
)


class BarrierDisciplineRule(Rule):
    name = "barrier-discipline"
    description = (
        "call-site-counter barrier names, XLA collectives, and "
        "conditional rendezvous on coordination paths"
    )
    seeds = COORD_SEEDS

    def run(self, ctx: LintContext) -> Iterable[Finding]:
        graph = ctx.callgraph
        marked = coord_sites(graph)
        scope = seed_scope(
            graph,
            list(COORD_SEEDS)
            + [(rel, qual) for rel, qual in sorted(marked)],
        )
        envs: Dict[str, object] = {}
        for key in sorted(scope):
            info = graph.funcs[key]
            sf = info.module
            env = envs.setdefault(sf.relpath, module_env(sf))
            yield from self._check_minting(key, info, sf, env)
            yield from self._check_collectives(key, info, sf, env)
            yield from self._check_conditional(key, info, sf)
        # seq-less _process_barrier is a call-site property — checked
        # everywhere, scope or not (the runner's final barrier is the
        # sanctioned exception, suppressed in place).
        yield from self._check_seqless_barrier(ctx, scope, graph)

    # -- counter-minted names ------------------------------------------

    def _is_mint_call(self, node: ast.AST, env) -> Optional[str]:
        """Human label when ``node`` is a call minting a call-site
        value: _barrier_seq / next / time.time / os.getpid / id."""
        if not isinstance(node, ast.Call):
            return None
        fn = node.func
        if isinstance(fn, ast.Name):
            if fn.id == "_barrier_seq" or env.from_imports.get(
                fn.id, ("", "")
            )[1] == "_barrier_seq":
                return "_barrier_seq(...)"
            if fn.id == "next" and node.args:
                return "next(...)"
            if fn.id == "id" and node.args:
                return "id(...)"
            if env.from_imports.get(fn.id) in _MINT_TIME:
                return f"{fn.id}(...)"
        elif isinstance(fn, ast.Attribute):
            if fn.attr == "_barrier_seq":
                return "_barrier_seq(...)"
            if isinstance(fn.value, ast.Name):
                mod = env.mod_aliases.get(fn.value.id)
                if (mod, fn.attr) in _MINT_TIME:
                    return f"{mod}.{fn.attr}()"
        return None

    def _check_minting(self, key, info, sf, env) -> Iterable[Finding]:
        if key[1].rsplit(".", 1)[-1] == "_barrier_seq":
            return  # the mint helper's own body is not a mint SITE
        # taint: local name -> (mint line, mint label). Assignments
        # are processed in SOURCE order (own_statements walks in stack
        # order) so taint propagates through `seq = mint(); key =
        # f"...{seq}"` chains.
        taint: Dict[str, Tuple[int, str]] = {}
        assigns = sorted(
            (
                n
                for n in own_statements(info.node)
                if isinstance(n, (ast.Assign, ast.AnnAssign))
            ),
            key=lambda n: n.lineno,
        )
        for node in assigns:
            value = node.value
            if value is None:
                continue
            origin = None
            for sub in ast.walk(value):
                label = self._is_mint_call(sub, env)
                if label is not None:
                    origin = (node.lineno, label)
                    break
                if isinstance(sub, ast.Name) and sub.id in taint:
                    origin = taint[sub.id]
                    break
            if origin is None:
                continue
            targets = (
                node.targets
                if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                if isinstance(t, ast.Name):
                    taint[t.id] = origin

        emitted: Set[Tuple[int, str]] = set()
        for node in own_statements(info.node):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (
                isinstance(fn, ast.Attribute)
                and fn.attr in _COORD_OPS
            ):
                continue
            if not node.args:
                continue
            name_arg = node.args[0]
            for sub in ast.walk(name_arg):
                origin = None
                if isinstance(sub, ast.Name) and sub.id in taint:
                    origin = taint[sub.id]
                else:
                    label = self._is_mint_call(sub, env)
                    if label is not None:
                        origin = (node.lineno, label)
                if origin is None:
                    continue
                line, label = origin
                if (line, label) in emitted:
                    continue
                emitted.add((line, label))
                yield Finding(
                    self.name, sf.relpath, line,
                    f"barrier/KV name in `{key[1]}` derives from "
                    f"call-site mint `{label}` — one asymmetric "
                    "failure desyncs the counters across processes "
                    "and wedges every later rendezvous (PR-13 wedge "
                    "class); derive the name from an enqueue-time "
                    "job sequence passed in as a parameter",
                )
        # a mint interpolated straight into ANY name string (f-string)
        # is flagged even when the consumer is out of lexical sight
        # (orbax's barrier_prefix): the minted prefix IS the name.
        for node in own_statements(info.node):
            if not isinstance(node, ast.JoinedStr):
                continue
            for sub in ast.walk(node):
                label = self._is_mint_call(sub, env)
                if label is None or label != "_barrier_seq(...)":
                    continue
                if (node.lineno, label) in emitted:
                    continue
                emitted.add((node.lineno, label))
                yield Finding(
                    self.name, sf.relpath, node.lineno,
                    f"barrier-name string in `{key[1]}` interpolates "
                    f"call-site mint `{label}` — names must derive "
                    "from an enqueue-time job sequence (PR-13 wedge "
                    "class)",
                )

    def _check_seqless_barrier(
        self, ctx, scope, graph
    ) -> Iterable[Finding]:
        for key in sorted(graph.funcs):
            info = graph.funcs[key]
            if key[1].rsplit(".", 1)[-1] == "_process_barrier":
                continue
            sf = info.module
            for node in own_statements(info.node):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                name = (
                    fn.id
                    if isinstance(fn, ast.Name)
                    else fn.attr
                    if isinstance(fn, ast.Attribute)
                    else ""
                )
                if name != "_process_barrier":
                    continue
                seq = next(
                    (
                        kw.value
                        for kw in node.keywords
                        if kw.arg == "seq"
                    ),
                    node.args[1] if len(node.args) > 1 else None,
                )
                if seq is not None and not (
                    isinstance(seq, ast.Constant)
                    and seq.value is None
                ):
                    continue
                yield Finding(
                    self.name, sf.relpath, node.lineno,
                    f"`_process_barrier(...)` without `seq=` in "
                    f"`{key[1]}` — falls back to the per-tag "
                    "call-site counter, which is only safe at sites "
                    "every process reaches the same number of times; "
                    "pass the enqueue-time job sequence (or suppress "
                    "with the reason the site is symmetric)",
                )

    # -- XLA collectives on coordination paths -------------------------

    def _check_collectives(
        self, key, info, sf, env
    ) -> Iterable[Finding]:
        for node in own_statements(info.node):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            hit = None
            if isinstance(fn, ast.Attribute):
                if fn.attr in _COLLECTIVE_ANY_BASE:
                    hit = fn.attr
                elif fn.attr in _COLLECTIVE_LAX and isinstance(
                    fn.value, ast.Name
                ):
                    base = fn.value.id
                    if env.mod_aliases.get(base) == "jax.lax" or (
                        env.from_imports.get(base) == ("jax", "lax")
                    ):
                        hit = f"lax.{fn.attr}"
            elif isinstance(fn, ast.Name):
                imp = env.from_imports.get(fn.id)
                if imp is not None and (
                    imp[1] in _COLLECTIVE_ANY_BASE
                    or (
                        imp[0].endswith("multihost_utils")
                        and imp[1] in _COLLECTIVE_LAX
                    )
                    or (imp[0] == "jax.lax" and imp[1] in _COLLECTIVE_LAX)
                ):
                    hit = imp[1]
            if hit is None:
                continue
            yield Finding(
                self.name, sf.relpath, node.lineno,
                f"XLA collective `{hit}` on coordination path "
                f"`{key[1]}` — a collective from a coordination thread "
                "queues device work behind the step stream; use the "
                "coordination-service KV store "
                "(docs/DURABILITY.md)",
            )

    # -- conditional rendezvous ----------------------------------------

    def _check_conditional(self, key, info, sf) -> Iterable[Finding]:
        found: List[Finding] = []

        def is_barrier_wait(node: ast.AST) -> Optional[str]:
            if not isinstance(node, ast.Call):
                return None
            fn = node.func
            name = (
                fn.id
                if isinstance(fn, ast.Name)
                else fn.attr
                if isinstance(fn, ast.Attribute)
                else ""
            )
            return name if name in _BARRIER_WAITS else None

        def test_is_asymmetric(test: ast.AST) -> bool:
            for sub in ast.walk(test):
                if (
                    isinstance(sub, ast.Attribute)
                    and sub.attr == "process_index"
                ) or (
                    isinstance(sub, ast.Name)
                    and sub.id == "process_index"
                ):
                    return True
            return False

        def walk(stmts, under: bool):
            for stmt in stmts:
                if isinstance(
                    stmt,
                    (
                        ast.FunctionDef,
                        ast.AsyncFunctionDef,
                        ast.ClassDef,
                    ),
                ):
                    continue
                inner = under
                if isinstance(stmt, ast.If) and test_is_asymmetric(
                    stmt.test
                ):
                    inner = True
                if inner:
                    for sub in ast.walk(stmt):
                        name = is_barrier_wait(sub)
                        if name is not None:
                            found.append(
                                Finding(
                                    self.name,
                                    sf.relpath,
                                    sub.lineno,
                                    f"barrier wait `{name}` under a "
                                    f"`process_index` test in "
                                    f"`{key[1]}` — one process skips "
                                    "a rendezvous its peers perform; "
                                    "they hang until the "
                                    "coordination timeout. Hoist the "
                                    "wait out of the branch "
                                    "(asymmetric KV set/get is fine; "
                                    "the rendezvous is not)",
                                )
                            )
                    continue
                for field in ("body", "orelse", "finalbody"):
                    suite = getattr(stmt, field, ()) or ()
                    if suite:
                        walk(list(suite), inner)
                for h in getattr(stmt, "handlers", ()) or ():
                    walk(h.body, inner)

        walk(list(info.node.body), False)
        return found
