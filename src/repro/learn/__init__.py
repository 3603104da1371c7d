"""Learned congestion control: gradient-through-sim training (paper §V).

The paper closes by calling for "an optimized, yet low-overhead,
congestion control scheme based on the characteristics of distributed
training platforms".  This package builds one end to end through the
differentiable fluid simulator:

* ``net``   — a tiny per-flow MLP policy over the engine's ``Signals``
  feedback + normalized ``FlowCtx`` context, its weights flattened into
  the ``ParamSpec`` currency (registered as the 8th policy ``"mlp"`` in
  ``repro.core.cc.REGISTRY``);
* ``train`` — an Adam loop on ``Simulator.soft_cost_fn(remat=True)``
  across a curriculum of ``ScenarioSpec``s (topologies x fault regimes x
  fabric corners), with per-scenario weighting, gradient clipping,
  non-finite guards and checkpoint/resume.
"""
from repro.learn.net import (HIDDEN, N_FEATURES, WEIGHT_KEYS,  # noqa: F401
                             default_weights, init_weights, make_mlp)
from repro.learn.train import (LearnResult, TrainConfig,  # noqa: F401
                               curriculum_default, heldout_default,
                               heldout_eval, load_checkpoint,
                               save_checkpoint, train)
