"""Architectures of the benchmark's configurations, one module each, found
by the name a configuration's model block gives (``"architecture"``;
``bench.model.arch``). A module provides:

- ``model_config(conf)``: the program's ``ModelConfig`` for the
  configuration file ``conf``;
- ``make_plain(m, key, dtype)``: the plain weights drawn from ``key``;
  ``to_program(m, plain)``: those weights in the program's parameter
  pytree; ``plain_view(m, params)``: its inverse, which the reference
  reads;
- ``forward(weights, tokens, *, mk, bits)``: the plain reference's (T, V)
  float32 logits of one sequence, with ``mk`` the model block as sorted
  (key, value) pairs and ``bits`` the control's grids
  (``bench.reference.linear``); it imports nothing of the program;
- ``layers(m)``: one dict per layer: ``heads``, ``kv_heads``,
  ``head_dim``, ``window`` (None for full attention) and ``linears``
  (the linears one token passes through, in call order, which are the
  int8 linears of the W8A8 path: name, K, N, whether the input is in PEG
  groups, output bytes per element, extra f32 input read per row; a
  token's multiply-adds in the layer are the sum of K N);
- ``packed_weight_bytes(m, groups)``: the HBM bytes of the W8A8 packing.
"""
