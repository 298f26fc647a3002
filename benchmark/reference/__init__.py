"""Plain references: jax.numpy, float32, matmuls at "highest". Nothing
here imports the program or takes anything the program has made."""
