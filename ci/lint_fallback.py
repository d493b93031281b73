#!/usr/bin/env python
"""Dependency-free lint pass (reference parity: ``.travis.yml:51-54``
runs flake8/autopep8; this image ships no linter, so CI enforces the
core rules with the stdlib and ``setup.cfg`` keeps the real flake8
config for environments that have it).

Checks: syntax (ast), line length <= 79, trailing whitespace, tabs in
indentation, unused ``import x`` / ``from x import y`` bindings at
module scope (noqa-comment aware), missing newline at EOF, bare
``except:`` (E722), mutable default arguments (B006), and -- inside
``chainermn_tpu/`` hot paths only -- ``jax.device_get`` /
``np.asarray`` calls (SHL01: either is a host sync when handed a
traced value; the eager driver-level uses are allow-listed with
``# noqa: shardlint``).
"""

import ast
import os
import sys

MAX_LEN = 79
EXCLUDE = {'.git', '__pycache__', 'build', 'docs', '.jax_compile_cache',
           'result', '.pytest_cache',
           # the chip benchmark's git-ignored scratch: a builder's copies
           # of other commits and its chip scripts are not the repo's code
           '.chipbench_copy', '.chipbench_cache', '.chipbench_trace'}
#: directories whose code runs per-iteration (traced or driving the
#: device loop) -- the SHL01 host-sync rule applies only here
HOT_PATHS = ('chainermn_tpu/communicators/', 'chainermn_tpu/training/',
             'chainermn_tpu/parallel/', 'chainermn_tpu/ops/')
#: calls that synchronize with the host when given a traced/device
#: value: (module alias, attribute)
HOST_SYNC_CALLS = {('jax', 'device_get'), ('np', 'asarray'),
                   ('numpy', 'asarray')}


def iter_py(root):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in EXCLUDE
                       and not d.startswith('result')]
        for fn in filenames:
            if fn.endswith('.py'):
                yield os.path.join(dirpath, fn)


def unused_imports(tree, src_lines):
    names = {}  # alias -> (lineno, qualname)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                alias = a.asname or a.name.split('.')[0]
                names[alias] = (node.lineno, a.name)
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name == '*':
                    continue
                alias = a.asname or a.name
                names[alias] = (node.lineno, a.name)
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            pass  # the base Name is what binds
    out = []
    for alias, (lineno, qual) in sorted(names.items()):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ''
        if 'noqa' in line:
            continue
        if alias not in used:
            out.append((lineno, 'F401 %r imported but unused' % qual))
    return out


def _line_suppressed(src_lines, lineno, code=None):
    """True when the source line carries a ``noqa`` comment (bare, or
    scoped to ``code`` via ``# noqa: <code>``)."""
    line = src_lines[lineno - 1] if 0 < lineno <= len(src_lines) else ''
    if 'noqa' not in line:
        return False
    if code is None:
        return True
    mark = line[line.index('noqa'):]
    return ':' not in mark or code in mark


def ast_rules(tree, src_lines, hot_path):
    """AST-level rules: bare except, mutable defaults, and (hot paths
    only) host-sync calls."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            if not _line_suppressed(src_lines, node.lineno):
                out.append((node.lineno,
                            "E722 do not use bare 'except:'"))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defaults = (list(node.args.defaults)
                        + [d for d in node.args.kw_defaults
                           if d is not None])
            for d in defaults:
                mutable = isinstance(d, (ast.List, ast.Dict, ast.Set))
                if (isinstance(d, ast.Call)
                        and isinstance(d.func, ast.Name)
                        and d.func.id in ('list', 'dict', 'set')):
                    mutable = True
                if mutable and not _line_suppressed(src_lines,
                                                    d.lineno):
                    out.append((d.lineno,
                                'B006 mutable default argument'))
        elif (hot_path and isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and (node.func.value.id, node.func.attr)
                in HOST_SYNC_CALLS):
            if not _line_suppressed(src_lines, node.lineno,
                                    'shardlint'):
                out.append((
                    node.lineno,
                    'SHL01 %s.%s in a hot path: host sync if handed '
                    'a traced value (allow-list deliberate eager use '
                    'with `# noqa: shardlint`)'
                    % (node.func.value.id, node.func.attr)))
    return out


def lint_file(path):
    problems = []
    with open(path, 'rb') as f:
        raw = f.read()
    if raw and not raw.endswith(b'\n'):
        problems.append((len(raw.splitlines()), 'W292 no newline at EOF'))
    try:
        src = raw.decode('utf-8')
    except UnicodeDecodeError as e:
        return [(0, 'E902 %s' % e)]
    lines = src.splitlines()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [(e.lineno or 0, 'E999 %s' % e.msg)]
    for i, line in enumerate(lines, 1):
        if 'noqa' in line:
            continue
        if len(line) > MAX_LEN:
            problems.append((i, 'E501 line too long (%d > %d)'
                             % (len(line), MAX_LEN)))
        if line != line.rstrip():
            problems.append((i, 'W291 trailing whitespace'))
        stripped = line.lstrip(' ')
        if stripped.startswith('\t') or line.startswith('\t'):
            problems.append((i, 'W191 tab in indentation'))
    problems.extend(unused_imports(tree, lines))
    norm = os.path.abspath(path).replace(os.sep, '/')
    hot = any(hp in norm for hp in HOT_PATHS)
    problems.extend(ast_rules(tree, lines, hot))
    return sorted(problems)


def main(root='.'):
    total = 0
    for path in sorted(iter_py(root)):
        for lineno, msg in lint_file(path):
            print('%s:%d: %s' % (os.path.relpath(path, root), lineno, msg))
            total += 1
    print('%d problem(s)' % total)
    return 1 if total else 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else '.'))
