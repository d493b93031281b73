#!/usr/bin/env python
"""Data-parallel MNIST training.

TPU-native rebuild of the reference demo
(``examples/mnist/train_mnist.py``): same flags, same structure --
communicator, multi-node optimizer, scattered dataset, trainer with
evaluator/logging gated to rank 0 -- but launched as plain
``python train_mnist.py`` on a TPU slice (the JAX runtime replaces the
``mpiexec`` launcher; BASELINE.json north_star).
"""

import argparse
import os
import sys

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))
import chainermn_tpu  # noqa: E402
from chainermn_tpu.datasets import mnist
from chainermn_tpu.models import MLP, Classifier
from chainermn_tpu import training
from chainermn_tpu.training import extensions


def main():
    parser = argparse.ArgumentParser(description='ChainerMN-TPU MNIST')
    parser.add_argument('--batchsize', '-b', type=int, default=100,
                        help='global minibatch size')
    parser.add_argument('--communicator', type=str, default='xla',
                        help='communicator strategy name')
    parser.add_argument('--epoch', '-e', type=int, default=20)
    parser.add_argument('--unit', '-u', type=int, default=1000)
    parser.add_argument('--out', '-o', default='result')
    parser.add_argument('--resume', '-r', default='',
                        help='resume from a snapshot (.npz)')
    parser.add_argument('--cpu', action='store_true',
                        help='force the virtual CPU mesh (testing)')
    parser.add_argument('--mesh', type=str, default=None,
                        help='override mesh shape, e.g. 2x4')
    parser.add_argument('--profile', default='',
                        help='capture a device trace into this dir '
                             '(view in TensorBoard); the program\'s '
                             'own spans (cmn:train_update, '
                             'cmn:jitted_step, ...) are in it with no '
                             'further flag')
    parser.add_argument('--quick', action='store_true',
                        help='tiny run for smoke testing')
    parser.add_argument('--policy', default=None,
                        help='mixed-precision policy (bf16 | f16 | '
                             'f32): compute/reduce narrow, f32 master '
                             'weights (docs/mixed_precision.md)')
    args = parser.parse_args()

    if args.cpu:
        chainermn_tpu.utils.force_host_devices(8)

    mesh_shape = None
    if args.mesh:
        mesh_shape = tuple(int(v) for v in args.mesh.split('x'))

    comm = chainermn_tpu.create_communicator(args.communicator,
                                             mesh_shape=mesh_shape)
    if comm.rank == 0:
        print('==========================================')
        print('Num devices: {}'.format(comm.size))
        print('Mesh: inter={} intra={}'.format(comm.inter_size,
                                               comm.intra_size))
        print('Using {} communicator'.format(args.communicator))
        print('Num unit: {}'.format(args.unit))
        print('Global mini-batch size: {}'.format(args.batchsize))
        print('Num epoch: {}'.format(args.epoch))
        print('==========================================')

    policy = (chainermn_tpu.Policy.from_string(args.policy)
              if args.policy else None)
    model = MLP(n_units=args.unit, n_out=10,
                dtype=policy.compute_dtype if policy else None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 784), jnp.float32))
    clf = Classifier(model.apply)

    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-3), comm)

    train, test = mnist.get_mnist()
    # each process loads its shard; per-device sharding happens per batch
    train = chainermn_tpu.scatter_dataset(train, comm)
    test = chainermn_tpu.scatter_dataset(test, comm)

    if args.quick:
        train = chainermn_tpu.dataset.SubDataset(
            train, 0, min(500, len(train)))
        args.epoch = min(args.epoch, 2)

    train_iter = training.SerialIterator(train, args.batchsize)
    test_iter = training.SerialIterator(test, args.batchsize,
                                        repeat=False, shuffle=False)

    updater = training.StandardUpdater(
        train_iter, optimizer, clf, params, comm, has_aux=True,
        policy=policy)
    trainer = training.Trainer(updater, (args.epoch, 'epoch'),
                               out=args.out)

    evaluator = training.Evaluator(
        test_iter, clf.eval_metrics, lambda: updater.params, comm)
    evaluator = chainermn_tpu.create_multi_node_evaluator(evaluator, comm)
    trainer.extend(evaluator, trigger=(1, 'epoch'))

    if comm.rank == 0:
        trainer.extend(extensions.snapshot(), trigger=(1, 'epoch'))
        trainer.extend(extensions.LogReport())
        trainer.extend(extensions.PrintReport(
            ['epoch', 'loss', 'accuracy', 'validation/main/loss',
             'validation/main/accuracy', 'elapsed_time']),
            trigger=(1, 'epoch'))

    if args.resume:
        from chainermn_tpu import serializers
        serializers.resume_updater(args.resume, updater, comm)

    trainer.extend(chainermn_tpu.utils.NanGuard(), trigger=(1, 'iteration'))
    if args.profile:
        from chainermn_tpu.utils import profiling
        with profiling.trace(args.profile):
            trainer.run()
    else:
        trainer.run()
    if comm.rank == 0:
        print('final observation:', {
            k: v for k, v in trainer.observation.items()})
    return trainer


if __name__ == '__main__':
    main()
