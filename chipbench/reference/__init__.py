"""Plain references of the benchmark's models: the forward pass in
straightforward `jax.numpy` float32, independent of the program."""
