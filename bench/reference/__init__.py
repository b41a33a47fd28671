"""Plain references, one module per model family: ``<name>.py``.

A configuration file names its reference in ``"reference": "<name>"``, and
`bench.run_cell.load_cell` loads ``bench/reference/<name>.py`` by that name,
as it loads the metric readers and kernel costs.  A reference imports
nothing of the program under test and takes nothing the program made.  It
exports:

  * ``FAMILIES``: the registry's ``ModelConfig.family`` values whose math
    it implements.  A configuration whose ``arch`` is of another family is
    refused at load.
  * ``shape_of(config) -> shape``: the sizes it needs, from the
    configuration file (a hashable value).
  * ``served_gaps(seed, shape, requests, chunk) -> list[np.ndarray]``: the
    weights drawn again from ``seed``; for each (prompt ids, served ids)
    pair, float32 [n_served], how far each served token's logit lies below
    the reference's best at the position that produced it, prompts taken
    in the scheduler's chunks of ``chunk``.
  * ``lane_bytes(shape, position, tokens) -> int``: the bytes of one
    lane's own state that a step moves, read plus written, when it takes
    in ``tokens`` tokens at positions ``position`` .. ``position + tokens
    - 1`` (0-based): its recurrent state read and written once, any cached
    keys and values of the positions below ``position`` read once, and
    those of its own tokens written once.  A decode step takes in one
    token; a prompt chunk, its length.
"""
