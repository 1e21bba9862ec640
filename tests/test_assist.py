"""Averaging-assist aux mode (swarm/assist.py + the weight-0 member
protocol in swarm/allreduce.py): the reference declares this mode and
stubs it with NotImplementedError (run_aux_peer.py:99-104); here it is
implemented and these tests pin its semantics."""

import threading
import time

import numpy as np
import pytest

from dalle_tpu.swarm import compression
from dalle_tpu.swarm.allreduce import flatten_tensors, run_allreduce
from dalle_tpu.swarm.assist import (AveragingAssistant, assist_one_round,
                                    grad_flat_elements)
from dalle_tpu.swarm.matchmaking import make_group
from tests.test_collab import make_swarm, run_threads


@pytest.fixture
def swarm3():
    nodes = make_swarm(3)
    yield nodes
    for n in nodes:
        n.shutdown()


SHAPES = [(33,), (8, 9), (5,)]
N_ELEMS = 33 + 72 + 5


def _tensors(seed):
    rng = np.random.RandomState(seed)
    return [rng.randn(*s).astype(np.float32) for s in SHAPES]


class TestWeightZeroProtocol:
    def test_assistant_owns_part_result_excludes_it(self, swarm3):
        """2 trainers + 1 weight-0 assistant: trainers' results equal the
        weighted mean of the TRAINERS only, identical on both — which
        also proves the assistant reduced and gathered its part (a dead
        or wrong part would leave the trainers' copies divergent)."""
        tensors = [_tensors(0), _tensors(1)]
        weights = [1.0, 3.0]

        def trainer(i):
            # assist_one_round joins the "<run_id>_grads" prefix the
            # collaborative optimizer uses — trainers here do the same
            g = make_group(swarm3[i], "as_grads", epoch=0,
                           weight=weights[i],
                           matchmaking_time=3.0, min_group_size=2)
            assert g is not None and g.size == 3
            # every routable member owns a part, assistant included
            assert sum(1 for m in g.members if m.addr) == 3
            return run_allreduce(swarm3[i], g, "as_grads", 0, tensors[i],
                                 weight=weights[i], allreduce_timeout=10.0,
                                 codec=compression.NONE)

        def assistant():
            template = np.zeros(N_ELEMS, np.float32)
            outcome = assist_one_round(
                swarm3[2],
                _cfg(matchmaking_time=3.0, allreduce_timeout=10.0),
                0, template, codec=compression.NONE)
            assert outcome == "assisted", outcome

        results = run_threads([lambda: trainer(0), lambda: trainer(1),
                               assistant])
        num = (flatten_tensors(tensors[0]) * weights[0]
               + flatten_tensors(tensors[1]) * weights[1])
        want = num / sum(weights)
        for res in results[:2]:
            np.testing.assert_allclose(flatten_tensors(res), want,
                                       rtol=1e-5, atol=1e-6)

    def test_zero_sample_trainer_not_waited_on(self, swarm3):
        """A trainer that accumulated 0 samples contributes nothing and
        receivers must not wait on it — the round completes fast."""
        tensors = [_tensors(0), _tensors(1), _tensors(2)]
        weights = [2.0, 1.0, 0.0]

        def peer(i):
            g = make_group(swarm3[i], "zs", epoch=1, weight=weights[i],
                           matchmaking_time=3.0, min_group_size=2)
            assert g is not None and g.size == 3
            t0 = time.monotonic()
            res = run_allreduce(swarm3[i], g, "zs", 1, tensors[i],
                                weight=weights[i], allreduce_timeout=30.0,
                                codec=compression.NONE)
            return res, time.monotonic() - t0

        out = run_threads([lambda i=i: peer(i) for i in range(3)])
        num = sum(flatten_tensors(t) * w
                  for t, w in zip(tensors[:2], weights[:2]))
        want = num / sum(weights[:2])
        for res, dt in out[:2]:
            np.testing.assert_allclose(flatten_tensors(res), want,
                                       rtol=1e-5, atol=1e-6)
            # no sender_timeout (7.5 s at this budget) was burned waiting
            # for the 0-weight member's nonexistent contribution
            assert dt < 6.0, dt

    def test_assistant_with_no_contributions_withholds_part(self, swarm3):
        """An assistant whose contributors all die mid-round must NOT
        gather its zero template (that would silently zero the part on
        every trainer) — it withholds the part and reports the empty
        round so the loop can raise the config-mismatch alarm."""
        from dalle_tpu.swarm.allreduce import run_allreduce as ar

        def dead_trainer():
            # announce like a trainer, never serve the round
            g = make_group(swarm3[0], "wh", epoch=3, weight=1.0,
                           matchmaking_time=3.0, min_group_size=2)
            assert g is not None

        def assistant():
            g = make_group(swarm3[1], "wh", epoch=3, weight=0.0,
                           matchmaking_time=3.0, min_group_size=2)
            assert g is not None and g.size == 2
            report = {}
            template = [np.zeros(N_ELEMS, np.float32)]
            ar(swarm3[1], g, "wh", 3, template, weight=0.0,
               allreduce_timeout=5.0, codec=compression.NONE,
               report=report)
            assert report["reduced_senders"] == 0
            assert report["complete"] is False
            return report

        run_threads([dead_trainer, assistant])

    def test_assistant_death_degrades_like_dead_owner(self, swarm3):
        """An assistant that vanishes after matchmaking costs the
        trainers only its part's gather (local-fallback elasticity): the
        round returns and the surviving trainers' contributions still
        average."""
        tensors = [_tensors(0), _tensors(1)]

        def trainer(i):
            g = make_group(swarm3[i], "ad", epoch=2, weight=1.0,
                           matchmaking_time=3.0, min_group_size=2)
            assert g is not None and g.size == 3
            report = {}
            res = run_allreduce(swarm3[i], g, "ad", 2, tensors[i],
                                weight=1.0, allreduce_timeout=6.0,
                                codec=compression.NONE, report=report)
            return res, report

        def dead_assistant():
            # announce like an assistant, then never serve the round
            g = make_group(swarm3[2], "ad", epoch=2, weight=0.0,
                           matchmaking_time=3.0, min_group_size=2)
            assert g is not None

        out = run_threads([lambda: trainer(0), lambda: trainer(1),
                           dead_assistant])
        want = (flatten_tensors(tensors[0])
                + flatten_tensors(tensors[1])) / 2.0
        for res, report in out[:2]:
            flat = flatten_tensors(res)
            # the dead assistant's part fell back to local values; the
            # parts owned by live trainers are correctly averaged
            assert report["complete"] is False
            matches = np.isclose(flat, want, rtol=1e-5, atol=1e-6)
            assert 0 < matches.sum() < flat.size


def _cfg(**over):
    from dalle_tpu.config import CollabConfig
    return CollabConfig(run_id="as", encrypt_data_plane=False, **over)


class TestLeaderChoice:
    def test_assistant_never_leads_a_mixed_group(self):
        """Leader = lowest-id CONTRIBUTOR: views that differ only in
        which weight-0 assistants they saw elect the same leader, so an
        assistant's announce racing into some-but-not-all candidate
        views cannot splinter the round into two confirmed rosters."""
        from dalle_tpu.swarm.matchmaking import GroupMember, choose_leader

        def m(pid, w):
            return GroupMember(pid, f"127.0.0.1:{ord(pid[0])}", w, b"",
                               b"")

        trainers = [m("bbb", 2.0), m("ccc", 1.0)]
        assistant = m("aaa", 0.0)  # lowest id in the group
        with_a = sorted([assistant] + trainers, key=lambda x: x.peer_id)
        without = sorted(trainers, key=lambda x: x.peer_id)
        assert choose_leader(with_a).peer_id == "bbb"
        assert choose_leader(without).peer_id == "bbb"
        # an all-assistant lobby still has a deterministic leader
        assert choose_leader([assistant]).peer_id == "aaa"


class TestAssistantLoop:
    def test_grad_flat_elements_matches_param_count(self):
        from dalle_tpu.config import tiny_model_config
        from dalle_init import init_params
        from dalle_tpu.models.dalle import DALLE
        import jax

        cfg = tiny_model_config()
        n = grad_flat_elements(cfg)
        params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
        want = sum(np.prod(np.asarray(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
        assert n == int(want)

    def test_thread_assists_a_real_round(self, swarm3):
        """AveragingAssistant follows the progress tracker and joins the
        trainers' round; the trainers see a 3-member group."""
        from dalle_tpu.config import tiny_model_config
        from dalle_tpu.swarm.progress import ProgressTracker

        model_cfg = tiny_model_config()
        n = grad_flat_elements(model_cfg)
        cfg = _cfg(matchmaking_time=3.0, allreduce_timeout=10.0,
                   target_batch_size=8)

        assistant = AveragingAssistant(swarm3[2], cfg, model_cfg)
        sizes = []

        def trainer(i):
            rng = np.random.RandomState(i)
            tensors = [rng.randn(n).astype(np.float32)]
            tracker = ProgressTracker(swarm3[i], cfg.run_id,
                                      cfg.target_batch_size)
            tracker.report_local_progress(0, 8, force=True)
            # give the assistant's tracker poll a chance to see us
            time.sleep(1.0)
            g = make_group(swarm3[i], f"{cfg.run_id}_grads", 0,
                           weight=8.0,
                           matchmaking_time=cfg.matchmaking_time,
                           min_group_size=2)
            assert g is not None
            sizes.append(g.size)
            return run_allreduce(swarm3[i], g, f"{cfg.run_id}_grads", 0,
                                 tensors, weight=8.0,
                                 allreduce_timeout=cfg.allreduce_timeout,
                                 codec=compression.NONE)

        assistant.start()
        try:
            results = run_threads([lambda: trainer(0),
                                   lambda: trainer(1)])
            assert sizes == [3, 3]
            np.testing.assert_allclose(
                flatten_tensors(results[0]), flatten_tensors(results[1]),
                rtol=1e-6, atol=1e-7)
            # the assistant's own round trails the trainers' (it may sit
            # out the rest of its matchmaking window first)
            deadline = time.monotonic() + 20.0
            while assistant.rounds_assisted < 1:
                assert time.monotonic() < deadline, \
                    "assistant never assisted"
                time.sleep(0.1)
        finally:
            assistant.stop()
            assistant.join(timeout=30.0)
        assert not assistant.is_alive()
