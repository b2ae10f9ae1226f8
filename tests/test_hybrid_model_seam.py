"""`HybridParallelTrainStep` takes the model it is handed: GPT through the
seam gives the losses it gave before the seam (bit for bit: recorded on the
parent commit), and a model that runs on one device refuses the other axes
by name."""
import jax
import numpy as np
import pytest

from paddle_tpu.models import mellum
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.parallel import hybrid
from paddle_tpu.parallel.hybrid import HybridParallelTrainStep

# GPTConfig.tiny(), seed 3, three batches of RandomState(0).randint(0, 512,
# (4, 32)): the parent commit's losses (float.hex of the float32)
PARENT_LOSSES = ["0x1.8e750e0000000p+2", "0x1.9030ee0000000p+2",
                 "0x1.900e340000000p+2"]


def _batches(vocab, shape, n=3):
    rng = np.random.RandomState(0)
    return [rng.randint(0, vocab, shape).astype(np.int32) for _ in range(n)]


def test_gpt_through_the_seam_gives_the_parents_losses_bit_for_bit():
    cfg = GPTConfig.tiny()
    step = HybridParallelTrainStep(cfg, seed=3, devices=jax.devices()[:1])
    assert isinstance(step.model, hybrid._GPTModel)
    got = [float(step(ids)).hex() for ids in _batches(cfg.vocab_size,
                                                      (4, 32))]
    assert got == PARENT_LOSSES
    assert step.tally_stats() is None and step.last_chosen is None


def test_gpt_decays_what_it_decayed():
    step = HybridParallelTrainStep(GPTConfig.tiny(), seed=0,
                                   devices=jax.devices()[:1])
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(step.params)[0]]
    decayed = {n for n, d in zip(names, step._decays) if d}
    assert decayed == {"['wte']", "['wpe']"} | {
        f"['blocks']['{k}']" for k in ("wq", "wk", "wv", "wo", "w_up",
                                       "w_down")}


def test_a_handed_in_model_trains_and_keeps_its_tally():
    cfg = mellum.MellumConfig.tiny(experts_held=(0, 1, 2))
    step = HybridParallelTrainStep(mellum.MellumTrainModel(cfg), seed=3,
                                   lr=1e-3, devices=jax.devices()[:1])
    # made on the device, float32, the model's own leaves
    assert jax.tree_util.tree_structure(step.params) == \
        jax.tree_util.tree_structure(mellum.param_specs(cfg))
    losses = [float(step(ids)) for ids in _batches(cfg.vocab_size, (2, 64),
                                                   4)]
    assert all(np.isfinite(losses))
    assert step.last_chosen.shape == (4, 128, 2)
    t = step.tally_stats()
    # 4 steps x 4 layers x 128 tokens x 2 experts
    assert t["pairs_routed"] == 4 * 4 * 128 * 2
    assert t["experts_held"] == [0, 1, 2]
    assert t["pairs_held"] == sum(map(sum, t["held_counts"]))
    assert 0 < t["pairs_held"] < t["pairs_routed"]
    last = np.asarray(step.last_chosen)
    assert last.min() >= 0 and last.max() < cfg.num_experts
    # norms' gains do not decay, matrices do
    names = [path[-1].key for path, _ in
             jax.tree_util.tree_flatten_with_path(step.params)[0]]
    assert {n for n, d in zip(names, step._decays) if not d} == {
        "norm", "input_layernorm", "post_attention_layernorm", "q_norm",
        "k_norm"}


@pytest.mark.parametrize("router,over", [("seeded", 0), ("onto_held", 3)])
def test_the_tally_counts_the_steps_over_the_rows_bound(monkeypatch, router,
                                                        over):
    """Beside its tally the trainer counts, by layer, the steps whose held
    pairs passed the expert layer's bound on its sorted rows
    (`moe.held_rows_bound`, from the same shapes): none on the seeded
    weights, every layer of every step for a router forced onto the held
    experts (replaced as the benchmark's fault tools replace one)."""
    from paddle_tpu.parallel import moe
    cfg = mellum.MellumConfig.tiny(experts_held=(0, 1))
    if router == "onto_held":
        real = moe.softmax_topk_route

        def onto_held(h, wg, bias, top_k, *a, **kw):
            sel, g = real(h, wg, bias, top_k, *a, **kw)
            return jax.numpy.broadcast_to(
                jax.numpy.arange(top_k, dtype=sel.dtype), sel.shape), g
        monkeypatch.setattr(moe, "softmax_topk_route", onto_held)
    step = HybridParallelTrainStep(mellum.MellumTrainModel(cfg), seed=3,
                                   lr=1e-6, devices=jax.devices()[:1])
    before = step.tally_stats()
    assert before["steps"] == 0 and before["rows_bound"] is None
    assert before["layer_steps_over_bound"] == [0] * 4
    for ids in _batches(cfg.vocab_size, (2, 256), 3):
        step(ids)
    t = step.tally_stats()
    # 1,024 pairs a layer, a quarter of the experts held: twice 256 rows
    assert t["rows_bound"] == 512 == moe.held_rows_bound(512, 2, 2, 8)
    assert t["steps"] == 3
    assert t["layer_steps_over_bound"] == [over] * 4
    held_a_layer = [sum(row) for row in t["held_counts"]]
    if over:
        assert held_a_layer == [3 * 1024] * 4
    else:
        assert max(held_a_layer) <= 3 * t["rows_bound"]


@pytest.mark.parametrize("axis", ["pp", "tp", "ep", "sp", "dp"])
def test_the_new_model_refuses_other_axes_by_name(axis):
    cfg = mellum.MellumConfig.tiny()
    with pytest.raises(NotImplementedError, match=f"{axis}=2 needs"):
        HybridParallelTrainStep(mellum.MellumTrainModel(cfg), **{axis: 2})


def test_a_model_that_says_nothing_is_refused_too():
    class Bare:
        pass
    with pytest.raises(NotImplementedError, match="Bare does not run tp=2"):
        HybridParallelTrainStep(Bare(), tp=2)
