"""shardlint: jaxpr-level static analysis for distributed training.

The ChainerMN reference pinned collective correctness dynamically by
running its whole suite under ``mpiexec -n {1,2,3}``; in this
TPU-native rebuild the sharding decisions live in traced code, so the
same invariants are PROVEN statically: each communicator strategy's
collective surface and each train step is traced with
``jax.make_jaxpr`` (no device computation, CPU-only) and the jaxpr is
walked -- recursing into ``jit``/``shard_map``/``scan``/``cond``
sub-jaxprs -- against the rule catalogue in
:mod:`chainermn_tpu.analysis.rules` (see ``docs/static_analysis.md``).

CLI: ``python -m chainermn_tpu.analysis [--json]`` sweeps all nine
registered strategies plus the example/updater/zero/pipeline steps;
``ci/run_staticcheck.sh`` wires it into the lint gate.
"""

from chainermn_tpu.analysis.findings import (  # noqa
    Finding, Report, SEV_ERROR, SEV_WARNING)
from chainermn_tpu.analysis.rules import RULES, RuleContext  # noqa
from chainermn_tpu.analysis.runner import (  # noqa
    build_report, lint_target, trace_target)
from chainermn_tpu.analysis.targets import (  # noqa
    LintTarget, STEP_FACTORIES, default_targets, step_targets,
    strategy_targets)
from chainermn_tpu.analysis import commcheck  # noqa
from chainermn_tpu.analysis import memtraffic  # noqa
from chainermn_tpu.analysis.commcheck import (  # noqa
    match_p2p, run_commcheck, verify_streams)
