"""Plain references of the served models, one module per architecture.

A configuration file names its reference in ``"reference"``.  The module
gives ``make_params(cfg, key, dtype)``, the random weights the harness
serves, built from the seed in the layout the serving program takes,
and ``logits(params, cfg, tokens, rows, mode)``, the reference's logits
at ``rows`` of one sequence.  A reference imports nothing of the
program.
"""
