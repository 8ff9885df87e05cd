"""Plain float32 references of the benchmark's model configurations.

Each module follows the published description of its architecture in
straightforward ``jax.numpy`` and imports nothing of the program.  It reads
the weights by the names of the program's parameter tree (as a checkpoint
loader would) and draws them itself from the seed (``init``).  Every module
exposes ``init(key, shapes, model)`` and ``loss(params, tokens, model, q)``,
where ``q`` rounds each matmul operand (identity for the reference, a lower
precision for the control).
"""
