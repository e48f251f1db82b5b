"""The yardstick: cells, traffic, metrics and the trace reduction.

Everything a later PR is measured with lives here and under
``tests/benchmark`` (`BENCHMARK.json` names both in ``paths``), so no PR
that claims a gain can change how the gain is counted.  From the program
the benchmark takes the system under test (`build_model`, `make_trainer`,
`trainer.train()`), its `obs` spans and its `jax.named_scope` names, and
nothing else.  `benchmark/README.md` says how to add a cell as data.
"""
