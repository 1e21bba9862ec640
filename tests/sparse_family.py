"""What every configuration of the sparse family (models/sparse_lm.py) must
pass, stated once; pytest collects nothing here. A configuration's file,
``tests/test_<configuration>_model.py``, is a subclass of :class:`Family`
whose attributes are its **row**, and the tests of what only it has.

**The recipe: what a ``model_config`` PR writes for a new configuration.**

1. One entry of :data:`CHAIN`: its class, the parent, the fields it adds.
2. Its file (one a configuration: the driver spreads *files* over its
   workers, so the longest file bounds the wall), with ``TINY``, the fields
   of the tiny model (``KERNEL_WIDTHS`` over it where the kernels run,
   interpreted), its yardstick ``Y = Manifest().yardstick("<name>")`` and

       class Test<Preset>(Family):
           config, preset, preset_config, Y, TINY, KERNEL_WIDTHS
           MOVED, PRECISION, LOSS_WITHIN, LEAF_WITHIN   (the defaults do)
           EXPERT_LAYERS   the tiny model's, the prediction module's too
                           (0: a dense stack, which carries no moe_* entry)
           BLOCKWISE       its kinds of blockwise attention layer
           ADDED           the fields the class states beyond its parent's
           PUBLISHED       the source's widths, as the preset must state them
           REFUSAL         the words of the decode refusal

   and one method a case for what that case holds of this configuration
   alone, which names what it reads of what the case has already run as
   keyword arguments (``**_`` takes the rest): ``the_yardstick_also`` (the
   leaves the tree must and must not hold, the lowering record, the
   records' sentences), ``the_normal_path_also`` (the fragments the rows of
   the ring carry), ``the_class_also`` (flags, what ``validate`` refuses).
   :class:`MechanismsLeftOut` (``LEFT_OUT``, ``EVERYTHING``),
   :class:`SharesAddUp` (``SHARES``) and :class:`BlockOnTheTile` (``BLOCK``)
   are bases beside :class:`Family` for the configurations that have the
   table.
3. Tests of its own mechanisms, as functions of the file, on the helpers
   below.

It does **not** write again: ``as_file``, ``batch``, ``rel_l2``, ``params``,
``system``; its ``TINY`` as command-line words (:func:`flags` derives them);
the cases of the classes below; the chain's field counts. It carries no case
of another configuration's preset.

**One build a configuration and lowering.** :func:`params` and the jitted
program behind :func:`system` are made once a process and key. A case that
asks the lowering record what its own trace did passes ``anew=True`` (a
program traced then, which later cases share) or asks :func:`trace` and
takes the shared program; a case that patches a constant a trace reads
takes :func:`program`, a program of its own."""
import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.cli import run_aux_peer, run_inference, run_server, run_trainer
from dalle_tpu.config import (AfmoeLMConfig, JoyAILMConfig, KeyeLMConfig,
                              Lfm2MoeLMConfig, NemotronHLMConfig,
                              OuroLMConfig, Qwen3NextLMConfig, SparseLMConfig)
from dalle_tpu.models import attention, family, sparse_lm
from dalle_tpu.ops.pallas import grouped_matmul_kernels as grouped

# the chain of classes: a class's parent and the fields it states beyond the
# parent's. ``benchmark/configs/<c>.json`` holds ``asdict`` of its class, so
# no class may gain a key when a later one states a mechanism as fields
CHAIN = {SparseLMConfig: (None, 27), AfmoeLMConfig: (SparseLMConfig, 12),
         JoyAILMConfig: (AfmoeLMConfig, 8),
         Lfm2MoeLMConfig: (AfmoeLMConfig, 2),
         KeyeLMConfig: (AfmoeLMConfig, 7),
         NemotronHLMConfig: (AfmoeLMConfig, 11),
         Qwen3NextLMConfig: (AfmoeLMConfig, 8),
         OuroLMConfig: (AfmoeLMConfig, 7)}


def as_file(cfg):
    return json.loads(json.dumps(dataclasses.asdict(cfg)))


def batch(cfg, seed=0, n=2):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(2, cfg.vocab_text,
                                     (n, cfg.text_seq_len)), jnp.int32),
            jnp.asarray(rng.integers(0, cfg.vocab_image,
                                     (n, cfg.image_seq_len)), jnp.int32))


def rel_l2(a, b):
    """On the host: as jax code every shape of leaf is four compiles."""
    a, b = (np.asarray(x, np.float32).ravel() for x in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def leaves_within(grads, ref_grads, limit):
    """Every leaf of ``grads`` within ``limit`` of the leaf of that name."""
    ours, theirs = leaves(grads), leaves(ref_grads)
    assert ours.keys() == theirs.keys()
    for name in ours:
        assert rel_l2(ours[name], theirs[name]) < limit, name


@functools.cache
def params(cfg, seed=1, moved=True):
    """Seeded weights, once a process and configuration; with ``moved``
    every vector leaf (norm scales, biases, the mixer's ``dt_bias``,
    ``A_log`` and ``D``) is off its initial ones and zeros, so that each
    counts."""
    weights = sparse_lm.init_params(sparse_lm.build(cfg),
                                    jax.random.PRNGKey(seed))
    if not moved:
        return weights
    flat, tree = jax.tree.flatten(weights)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(flat))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(flat, keys)])


def program(cfg):
    """A jitted program of the caller's own, traced at its first call: what
    a case takes that has patched a constant the trace reads, and shares
    with nobody."""
    model = sparse_lm.build(cfg)
    return jax.jit(jax.value_and_grad(
        lambda p, text, image: model.apply(p, text, image), has_aux=True))


_PROGRAMS = {}


def system(cfg, weights, text, image, anew=False):
    """``((loss, aux), grads)`` of the configuration's model, by the jitted
    program of the configuration and the lowering (``_PALLAS_INTERPRET``
    and the matmul precision as they stand), made once a process; ``anew``:
    by a program traced in this call, which the caller's record sees and
    later calls share (so: by no caller that has patched what a trace
    reads; that one takes :func:`program`)."""
    key = (cfg, attention._PALLAS_INTERPRET,
           jax.config.jax_default_matmul_precision)
    if anew or key not in _PROGRAMS:
        _PROGRAMS[key] = program(cfg)
    return _PROGRAMS[key](weights, text, image)


def reference_loss(y, weights, text, image, model):
    return float(jax.jit(lambda p: y.loss_fn(p, text, image, model))(
        weights)[0])


def trace(cfg, weights=None):
    """The model's forward traced and nothing run: the lowering record and
    the records' sentences say what the trace did."""
    model, (text, image) = sparse_lm.build(cfg), batch(cfg)
    jax.eval_shape(lambda p: model.apply(p, text, image),
                   params(cfg) if weights is None else weights)


def a_pass_is_its_xla_lowering(cfg, took, refuse, within=(1e-6, 1e-5),
                               moved=True):
    """Loss and every gradient leaf of the model with a pass in its kernel
    (interpreted) against the same model once ``refuse()`` has patched the
    pass's rule, every other kernel running on both sides: the same f32
    model to its rounding. The first is the process's program and a trace
    for the caller's record, the second a program of its own; what ``took()``
    said after each comes back."""
    weights, (text, image) = params(cfg, moved=moved), batch(cfg)
    (loss, _), grads = system(cfg, weights, text, image)
    trace(cfg, weights)         # what a trace says to the caller's record
    taken = took()
    refuse()
    (ref_loss, _), ref_grads = program(cfg)(weights, text, image)
    assert float(loss) == pytest.approx(float(ref_loss), rel=within[0])
    leaves_within(grads, ref_grads, within[1])
    return taken, took()


def flags(tiny, cls):
    """``tiny`` as ``run_trainer``'s command-line words; what the class
    states with no flag (``no_flag``) stays the preset's own."""
    words = []
    for key, value in tiny.items():
        if key not in cls.no_flag:
            words += ["--" + key.replace("_", "-"), *map(str, (
                value if isinstance(value, tuple) else (value,)))]
    return words


def fields(cls):
    return {f.name for f in dataclasses.fields(cls)}


class Family:
    """The cases of every configuration, over the row its subclass states."""
    KERNEL_WIDTHS = {}
    MOVED, PRECISION = True, None
    # f32 on both sides; the program and the reference order their sums
    # differently (blockwise softmax, streamed head, sorted experts)
    LOSS_WITHIN, LEAF_WITHIN = 2e-6, 2e-5
    BLOCKWISE = {}      # the kinds of blockwise attention layer: the window
    #                     and the sub-tile a tile at the band's edge goes by
    NOT_NOUGHT_ELSEWHERE = ()   # of ``ADDED``: constants of the other classes
    REFUSAL_STOPS = 3

    @classmethod
    def tiny(cls, kernels=False, **fields):
        return cls.config(**{**cls.TINY, **(cls.KERNEL_WIDTHS if kernels
                                            else {}), **fields})

    def pytest_generate_tests(self, metafunc):
        if "mechanism" in metafunc.fixturenames:
            metafunc.parametrize("mechanism", list(self.LEFT_OUT))

    @pytest.mark.parametrize("kernels", [False, True])
    def test_loss_and_every_gradient_leaf_against_the_yardstick(
            self, kernels, monkeypatch, lowering_record):
        """The whole tiny model with every mechanism on; with ``kernels``
        at the widths its Pallas kernels take, interpreted. (A program of
        its own: the record is asked what this trace did.)"""
        cfg = self.tiny(kernels)
        cfg.validate()
        monkeypatch.setattr(attention, "_PALLAS_INTERPRET", kernels)
        weights, (text, image) = params(cfg, moved=self.MOVED), batch(cfg)
        with jax.default_matmul_precision(self.PRECISION):
            (loss, aux), grads = system(cfg, weights, text, image, anew=True)
            ref_loss, ref_grads = self.Y.loss_and_grads(weights, text, image,
                                                        as_file(cfg))
        assert float(loss) == pytest.approx(float(ref_loss),
                                            rel=self.LOSS_WITHIN)
        leaves_within(grads, ref_grads, self.LEAF_WITHIN)
        # the counters are the expert layers' only: a stack with none (a
        # row that states ``EXPERT_LAYERS`` 0) carries no ``moe_*`` entry
        if not self.EXPERT_LAYERS:
            assert not [name for name in aux if name.startswith("moe_")]
        else:
            assert float(aux["moe_dropped"]) == 0.0
            assert float(aux["moe_dense_calls"]) == (
                0.0 if kernels else self.EXPERT_LAYERS)
            assert 0 < float(aux["moe_assignments_here_pct"]) < 100
        # no gradient reaches a router's bias, on either side: exact zeros
        for side in (grads, ref_grads):
            for name, bias in leaves(side).items():
                if "router_bias" in name:
                    assert bias.shape == (cfg.num_experts,), name
                    assert not np.asarray(bias).any(), name
        # which lowering every blockwise layer took, asked of the record,
        # and the one backward kernel a tile over the band of its one tile
        shut = None if kernels else "no Mosaic backend"
        for kind, (window, sub) in self.BLOCKWISE.items():
            call = f"{kind} attention", (
                cfg.total_seq_len, cfg.num_heads * cfg.head_dim,
                cfg.num_kv_heads * cfg.head_dim)
            assert lowering_record.why_not(*call) == shut
            if kernels:
                assert lowering_record.recorded(*call) == {
                    "why_not": None, "split_backward": None,
                    "band": sparse_lm.kernels.band_account(1, 512, window,
                                                           sub)}
        self.the_yardstick_also(
            with_kernels=kernels, cfg=cfg, weights=weights, text=text,
            image=image,
            loss=loss, aux=aux, grads=grads, ref_grads=ref_grads,
            lowering_record=lowering_record, tree=weights["params"],
            shut=shut, said=sparse_lm.engagement_records(cfg))

    def the_yardstick_also(self, **told):
        """What the case holds of this configuration alone: a subclass
        names what it reads of what the case has run, and ``**_``."""

    def test_the_preset_trains_through_the_peers_normal_path(
            self, lowering_record):
        """``run_trainer --preset <preset>`` (+ the tiny fields as flags):
        the parser builds the preset's own class, TrainingTask the model
        its configuration names, and train_loop runs it with the swarm
        optimizer; the rows of the trainer's ring carry the model's records
        (from an empty record: the token-major sum has no gate, and another
        test's sum of these shapes in this process would be this model's
        too)."""
        from dalle_tpu.obs.trace import default_tracer
        from dalle_tpu.task import TrainingTask
        from dalle_tpu.training.loop import train_loop

        args = run_trainer.build_parser().parse_args(
            ["--preset", self.preset, *flags(self.TINY, self.config),
             "--per-device-batch", "1", "--grad-accum-steps", "2",
             "--target-batch-size", str(1 << 30), "--seed", "7"])
        configs = run_trainer.configs_from_args(args)
        preset = self.preset_config()
        assert configs[0] == self.tiny(**{
            k: getattr(preset, k) for k in self.TINY
            if k in self.config.no_flag})
        task = TrainingTask(*configs)
        assert family(task.model_cfg) is sparse_lm
        assert isinstance(task.model, sparse_lm.SparseLM)
        losses = []
        with task:
            train_loop(task, max_steps=3, warmup_steps=1,
                       on_step=lambda n, loss: losses.append(loss))
            names = list(leaves(task.collab_optimizer.state.params))
        assert len(losses) == 3 and all(np.isfinite(losses))
        rows = [r for r in default_tracer().dump()
                if r.get("plane") == "train"]
        warm = [r for r in rows if r["phase"] == "setup/warmup"][-1]["a"]
        steps = [r["a"] for r in rows if r["phase"] == "loop/step"][-3:]
        for row in steps:
            if not self.EXPERT_LAYERS:
                assert not [name for name in row if name.startswith("moe_")]
                continue
            assert row["moe_dropped"] == 0.0
            # no Mosaic backend here: the dense lowering in every expert
            # layer of every shard
            assert row["moe_dense_calls"] == (self.EXPERT_LAYERS
                                              * task.mesh.size)
        # the optimizer was told the expert axis by the configuration
        assert task.model_cfg.optimizer_stacking()["stacked_experts"] == \
            self.TINY.get("experts_held", 0)
        self.the_normal_path_also(task=task, names=names, warm=warm,
                                  steps=steps, losses=losses)

    def the_normal_path_also(self, **ran):
        """What the rows carry of this configuration alone."""

    def test_the_preset_is_a_class_of_its_own_and_the_parents_keep_theirs(
            self):
        """The accepted configurations' files hold ``asdict`` of their
        classes: what this class states as fields are class attributes
        there, off, and no key of theirs is new; the preset states the
        source's widths."""
        for cls, (parent, more) in CHAIN.items():
            assert len(fields(cls)) == more + (
                len(fields(parent)) if parent else 0), cls
            assert set(dataclasses.asdict(cls())) == fields(cls)
        parent = CHAIN[self.config][0]
        added = fields(self.config) - fields(parent) if parent else set()
        assert added == self.ADDED
        for other in CHAIN:
            if not issubclass(other, self.config):
                new = added - fields(other)
                assert not set(dataclasses.asdict(other())) & new
                assert not any(getattr(other(), name) for name in
                               new - set(self.NOT_NOUGHT_ELSEWHERE)), other
        cfg = self.preset_config()
        assert type(cfg) is self.config
        assert isinstance(cfg, parent or self.config)
        cfg.validate()
        for name, value in self.PUBLISHED.items():
            assert getattr(cfg, name) == value, name
        self.the_class_also(cfg, {
            a.dest for a in run_trainer.build_parser()._actions})

    def the_class_also(self, cfg, flags):
        """The flags, and what ``validate`` refuses."""

    @pytest.mark.parametrize("cli, argv", [
        (run_inference, ["--checkpoint-dir", "x", "--tokenizer-path", "y",
                         "--query", "a cat"]),
        (run_server, ["--random-init"]),
        (run_aux_peer, []),
    ])
    def test_entry_points_that_decode_refuse_the_preset_at_start(self, cli,
                                                                 argv):
        with pytest.raises(SystemExit) as refused:
            cli.main(["--preset", self.preset, *argv])
        message = str(refused.value)
        assert self.preset in message and "models/decode.py" in message
        for words in self.REFUSAL:
            assert words in message, words
        assert "\n" not in message                          # one sentence
        assert self.REFUSAL_STOPS is None \
            or message.count(".") <= self.REFUSAL_STOPS


class MechanismsLeftOut:
    """``LEFT_OUT``: what each mechanism is when it is left out of the
    REFERENCE: the keys of ``model`` where it has them, else
    ``patch(monkeypatch, model) -> model`` of the yardstick's module;
    ``EVERYTHING``: the fields of the model that has them all."""

    @classmethod
    @functools.cache
    def with_everything(cls):
        cfg = cls.config(**cls.EVERYTHING)
        cfg.validate()
        weights, (text, image) = cls.weights_with_everything(cfg), batch(cfg)
        (loss, aux), _ = system(cfg, weights, text, image)
        theirs = cls.for_the_reference(cfg, weights)
        whole = reference_loss(cls.Y, theirs, text, image, as_file(cfg))
        return cfg, theirs, text, image, float(loss), aux, whole

    @staticmethod
    def weights_with_everything(cfg):
        """The weights both sides run on: a subclass moves what an init
        leaves where its mechanisms do not count."""
        return params(cfg)

    @staticmethod
    def for_the_reference(cfg, weights):
        return weights

    def test_a_mechanism_left_out_is_told(self, mechanism, monkeypatch):
        """The system against the reference whole agrees; against the
        reference without the mechanism it does not (at least ten times
        the distance at which they agree)."""
        cfg, weights, text, image, loss, aux, whole = self.with_everything()
        assert loss == pytest.approx(whole, rel=2e-6)
        patch, without = self.LEFT_OUT[mechanism], as_file(cfg)
        without = (patch(monkeypatch, without) if callable(patch)
                   else dict(without, **patch))
        lacking = reference_loss(self.Y, weights, text, image, without)
        assert abs(lacking - loss) > 2e-5 * loss, mechanism
        self.left_out_also(mechanism, cfg, loss, aux)

    def left_out_also(self, mechanism, cfg, loss, aux):
        """What the case holds of this configuration's mechanisms alone."""


class SharesAddUp:
    """``SHARES``: how many shares, and the fields of the preset's
    deployment at a small width, without the kernels and with them
    (interpreted)."""

    @pytest.mark.parametrize("kernels", [False, True])
    def test_the_shares_add_up_to_the_uncut_layer(self, kernels,
                                                  monkeypatch):
        """Every share holds ``experts_held`` consecutive experts
        (``expert_offset`` 0, held, 2 held ...) and its layer returns its
        routed part plus the shared expert, where there is one, which all
        compute alike: the routed parts summed, plus the shared expert
        counted once, equal the reference's uncut layer, and every
        assignment is computed by exactly one share."""
        monkeypatch.setattr(attention, "_PALLAS_INTERPRET", kernels)
        shares, fields = self.SHARES[kernels]
        base, y = self.config(**fields), self.Y
        n, held = base.num_experts, base.experts_held
        assert n // held == shares
        rng = jax.random.split(jax.random.PRNGKey(3), 9)
        d, f, fs = base.hidden_size, base.expert_width, base.shared_width
        m = jax.random.normal(rng[0], (2, 28, d))
        kernel = lambda key, shape: {"kernel": jax.random.normal(key, shape)
                                     * 0.2}
        whole = {"router": jax.random.normal(rng[1], (d, n)),
                 "router_bias": 0.05 * jax.random.normal(rng[2], (n,)),
                 "experts": {
                     "gate": jax.random.normal(rng[3], (n, d, f)) * 0.2,
                     "up": jax.random.normal(rng[4], (n, d, f)) * 0.2,
                     "down": jax.random.normal(rng[5], (n, f, d)) * 0.2}}
        if fs:
            whole["shared"] = {"gate": kernel(rng[6], (d, fs)),
                               "up": kernel(rng[7], (d, fs)),
                               "down": kernel(rng[8], (fs, d))}
        if not base.expert_gated:       # two leaves an expert, two a block
            for block in ("experts", "shared"):
                whole.get(block, {}).pop("gate", None)
        if base.shared_expert_gate:     # a number a token on the shared one
            whole["shared_gate"] = jax.random.normal(
                jax.random.PRNGKey(4), (d,)) * 0.2
        shared = jnp.zeros_like(m)
        with jax.default_matmul_precision("highest"):
            if base.shared_expert_gate:
                shared = y.shared_part(m, whole)
                assert float(jnp.abs(shared - y.gated_block(
                    m, whole["shared"])).max()) > 0.01
            elif fs:
                shared = (y.gated_block if base.expert_gated
                          else y.ungated_block)(m, whole["shared"])
            if fs:
                assert float(jnp.abs(shared).max()) > 0.01
            want = y.whole_layer_experts(m, whole, as_file(base))
            routed, here = jnp.zeros_like(m), 0.0
            for share in range(shares):
                cfg = dataclasses.replace(base, expert_offset=held * share)
                layer = sparse_lm.ExpertLayer(cfg)
                mine = {"params": dict(whole, experts={
                    k: w[held * share: held * (share + 1)]
                    for k, w in whole["experts"].items()})}
                idx, p = layer.apply(mine, m, method="route")  # alike on all
                part, counters = layer.apply(mine, m, idx, p)
                assert float(jnp.abs(part).max()) > 0.01
                routed = routed + (part - shared)
                here += float(counters["here"])
        np.testing.assert_allclose(routed + shared, want, atol=5e-5)
        assert here == pytest.approx(1.0)   # every assignment, by one share
        np.testing.assert_allclose(jnp.sum(p, -1), base.route_scale,
                                   rtol=1e-6)
        # summing the shares' results as they come counts the shared expert
        # once a share: that is not the layer
        assert not fs or float(jnp.abs(
            routed + shares * shared - want).max()) > 0.01


class BlockOnTheTile:
    """``BLOCK``: ``fields`` of the model whose grouped kernels run
    (interpreted), ``vmem`` that two weight blocks do not fit, the
    ``refusal``'s words."""

    def test_the_expert_block_on_the_tile_is_the_same_model_to_the_last_bit(
            self, monkeypatch, lowering_record):
        """f32, the grouped kernels interpreted: loss, counters and every
        gradient leaf with the expert block's tile work in its kernels
        equal the products a direction with XLA code between them, which
        the model takes where the weight blocks do not fit VMEM (the limit
        shrunk), and says why. (The first is the process's program and a
        trace for this case's record; the second a program of its own,
        whose trace reads the patched limit.)"""
        monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
        cfg = self.config(**self.BLOCK["fields"])
        weights, (text, image) = params(cfg), batch(cfg)
        key = sparse_lm._block_key(cfg.hidden_size, cfg.expert_width,
                                   cfg.dtype, gated=cfg.expert_gated)
        said = lambda: lowering_record.recorded(sparse_lm.PRODUCTS_SITE,
                                                key)["why_not"]
        layout = lambda: sparse_lm.engagement_records(cfg)["moe_layout"]
        on_the_tile = system(cfg, weights, text, image)
        trace(cfg, weights)     # what a trace says to this case's record
        assert lowering_record.recorded(sparse_lm.PRODUCTS_SITE, key) == {
            "why_not": None}
        assert layout().endswith("; expert block: " + (
            sparse_lm.BLOCK_ON_THE_TILE if cfg.expert_gated
            else sparse_lm.UNGATED_ON_THE_TILE))
        monkeypatch.setattr(grouped, "_VMEM", self.BLOCK["vmem"])
        apart = program(cfg)(weights, text, image)
        assert self.BLOCK["refusal"] in said()
        assert layout().endswith("; expert block: " + (
            "three products a direction" if cfg.expert_gated
            else "two products a direction, not gated") + f" ({said()})")
        (loss, aux), _ = on_the_tile
        assert np.isfinite(float(loss))
        assert float(aux["moe_dense_calls"]) == 0.0
        assert 0.0 < float(aux["moe_tiles_active_pct"]) <= 100.0
        for a, b in zip(jax.tree.leaves(on_the_tile), jax.tree.leaves(apart),
                        strict=True):
            np.testing.assert_array_equal(a, b)
        self.the_block_also(cfg, aux, said())

    def the_block_also(self, cfg, aux, refusal):
        """What the case holds of this configuration's block alone."""
