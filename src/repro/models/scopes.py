"""The named scopes of the model step: one ``jax.named_scope`` per layer.

A scope's name lands in the HLO ``op_name`` of every op traced inside it,
forward, backward and recomputed alike, and a TPU's device trace carries
that name with each op. So a profile can charge the device time of each
op to the innermost scope on its name. The scopes apply in train, prefill
and decode; the residual adds, the layer scan's own bookkeeping and the
copies the compiler adds lie in none.

==================  ====================================================
Scope               Wraps
==================  ====================================================
``embed``           the token lookup (``model._embed``)
``norm``            the pre-norms ``ln1`` / ``ln2`` (``stack.apply_stack``)
``mamba.in_proj``   the in-projection and its split (``layers.mamba``)
``mamba.conv``      the causal depthwise conv
``mamba.ssd``       dt and a, the SSD scan (chunked, kernel or decode
                    recurrence) and the ``D_skip`` term
``mamba.out``       the gate, the gated ``out_norm`` and ``out_proj``
``attention``       ``layers.attention`` and ``layers.cross_attention``
``mlp``             ``layers.mlp``
``moe.route``       the router matmul and ``routing.route``
``moe.dispatch``    the one-hots, the dispatch and combine-weight einsums
                    and the einsum to the experts' input
``moe.experts``     the expert GEMMs (einsum or the ``moe_gmm`` kernel)
``moe.combine``     the einsum back to the tokens and the shared expert
``head``            the final norm, the head matmul and the loss
``optimizer``       ``optim.adamw_update``, clipping included
==================  ====================================================
"""

SCOPES = ("embed", "norm", "mamba.in_proj", "mamba.conv", "mamba.ssd",
          "mamba.out", "attention", "mlp", "moe.route", "moe.dispatch",
          "moe.experts", "moe.combine", "head", "optimizer")
