"""The solve stack's layers point one way.

``core.ladder`` (the deepening ladder) sits above lane dispatch
(``core.batch``), the sharded and mesh programs (``core.shard``,
``core.distributed``) and the single rung (``core.solver``); none of those
imports the ladder, and none of the five imports another from inside a
function — a function-level import is how a cycle hides.
"""
import ast
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORE = os.path.join(REPO, "src", "repro", "core")
FIVE = ("solver", "batch", "ladder", "shard", "distributed")


def _imports(name):
    """(module-level, nested) sets of the five that ``name`` imports."""
    tree = ast.parse(open(os.path.join(CORE, name + ".py")).read())
    top = set()
    nested = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mods = {node.module or ""} | {a.name for a in node.names}
        elif isinstance(node, ast.Import):
            mods = {a.name for a in node.names}
        else:
            continue
        hit = {m.rsplit(".", 1)[-1] for m in mods} & set(FIVE)
        (top if node in tree.body else nested).update(hit - {name})
    return top, nested


def test_core_imports_point_one_way():
    graph = {}
    for name in FIVE:
        top, nested = _imports(name)
        assert not nested, (name, nested)
        graph[name] = top
    for name in FIVE:
        assert "ladder" not in graph[name] or name == "ladder", name
    # acyclic: a topological order exists
    order, seen = [], set()

    def visit(n, path):
        assert n not in path, path + (n,)
        if n in seen:
            return
        for m in graph[n]:
            visit(m, path + (n,))
        seen.add(n)
        order.append(n)

    for name in FIVE:
        visit(name, ())
    assert order[-1] == "ladder"
    code = ("import sys; import repro.core.batch, repro.core.shard, "
            "repro.core.distributed, repro.core.solver; "
            "print('repro.core.ladder' in sys.modules)")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
