#!/usr/bin/env python3
"""The readings a limit is set from, on the chip, in one process:

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 3 --seconds 20

For each seed: one run of the cell (set-up, a short window at the
cell's own load, the comparison with the plain reference), printing
what sound runs of the program give; for the first ``--control-seeds``
of them also the control, the reference computed in the precision below
the configuration's (fp8 for bfloat16) in the program's place.  A limit
goes above the sound runs' largest and below the control's smallest.
The benchmark's own runs never run this."""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None):
    from chipbench import harness
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seeds', required=True)
    parser.add_argument('--control-seeds', type=int, default=3)
    parser.add_argument('--seconds', type=float, default=20.0)
    args = parser.parse_args(argv)
    spec = harness.Spec(args.workload)
    harness.place_compile_cache()
    import jax
    for i, seed in enumerate(int(s) for s in args.seeds.split(',')):
        result = harness.run_cell(spec, seed, args.seconds, 0,
                                  time.perf_counter(),
                                  control=i < args.control_seeds)
        print('readings ' + json.dumps({
            'workload': args.workload, 'seed': seed,
            'correct': result['correct'], 'sound': result['checks'],
            'control': result.get('control'),
            'metrics': {k: v['value']
                        for k, v in result['metrics'].items()}}),
            flush=True)
        # one process, many cells' worth of state: drop what the last
        # run compiled and held before the next one is built
        del result
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == '__main__':
    sys.exit(main())
