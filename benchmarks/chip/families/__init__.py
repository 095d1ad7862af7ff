"""One package per model family, found by the configuration's `family`.

`families/<family>/reference.py` holds what the benchmark makes and
checks for that family and imports nothing of the program: MODEL_KEYS
(the configuration's keys that shape the model), make_shards(key,
workers, cfg, mesh) (the workers' data from the seed, (K, n_k, ...)),
init_params(key, cfg) (the weights, in the program's parameter tree),
noise(key, n, cfg) (the generator's input), and generator(gen, z, cfg,
variant) and discriminator(disc, x, cfg, variant), at
`Precision.HIGHEST`, where variant "fp8" is the family's precision one
step below the configuration's. `families/<family>/program.py` is the
family's only file that imports the program: spec(cfg) returns the
program's `GanModelSpec`. `flops/<family>.py` counts a round's FLOPs
and the Algorithm-2 bytes.
"""
