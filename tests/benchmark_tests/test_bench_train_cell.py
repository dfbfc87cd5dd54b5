"""The training cell at a toy size on the CPU: the harness drives the real
``ParallelTrainer`` and proves it against the plain reference; with the
timed path broken underneath, or the control in the program's place,
``correct`` comes out false."""
import numpy as np
import pytest

import bench_tiny
from benchmark import run
from benchmark.entry import train
from benchmark.systems.bert import Trainer

CELL = 'bert-tiny-pretrain'


@pytest.fixture(scope='module')
def tree(tmp_path_factory):
    return bench_tiny.build(tmp_path_factory.mktemp('bench_train'))


def _context(tree, seed=5):
    return bench_tiny.context(tree, CELL, seed, 0.3)


def test_train_cell_proves_correct(tree):
    line = run.execute(CELL, 2 ** 31 + 7, 0.3, 0, require_chip=False,
                       root=tree)
    assert line['correct'], line['compared']
    assert line['failed'] == 0 and line['attempted'] >= 1
    assert set(line['metrics']) == {'train_samples_per_s', 'setup_s'}
    assert list(line)[-1] == 'compared'
    for name in ('loss_gap_step1', 'grad_norm_gap', 'update_norm_gap'):
        assert line['compared'][name]['value'] \
            <= line['compared'][name]['limit']


class StateUnchanged(Trainer):
    """A step that returns its state unchanged."""

    def step(self, batch):
        x, y = self._feed(batch)
        self.pt.build(x, y)
        before = self.pt.snapshot()
        loss = self.pt.step(x, y)
        loss.wait_to_read()
        self.pt.restore(before)
        return loss


class HalfBatch(Trainer):
    """Half of the batch left out, the mean taken over the rest."""

    def step(self, batch):
        half = {k: np.concatenate([v[:len(v) // 2]] * 2)
                for k, v in batch.items()}
        return super().step(half)


@pytest.mark.parametrize('broken,number', [
    (StateUnchanged, 'update_norm_gap'), (HalfBatch, 'grad_norm_gap')])
def test_broken_step_is_not_correct(tree, broken, number):
    facts = train.run(_context(tree), build=broken)
    verdict = facts['verdict']
    assert not verdict.correct
    assert not verdict.rows[number]['ok'], verdict.rows


def test_control_precision_is_not_correct(tree):
    readings = train.control(_context(tree, seed=9))
    assert not readings['control_float8_e4m3fn']['correct'], readings
    assert not readings['fault_half_batch']['correct'], readings
