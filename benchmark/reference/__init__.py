"""Plain float32 references, one per model family, independent of the
program's model code: straightforward ``jax.numpy`` following the published
description, matmul precision ``highest``, no kernel, no mesh, no compute
dtype. They read the program's parameter tree by its checkpoint names and
nothing else from it."""
