"""One package per model family, found by the configuration's `family`.

`families/<family>/reference.py` holds what the benchmark makes and
checks for that family and imports nothing of the program: MODEL_KEYS
(the configuration's keys that shape the model; each may hold any JSON
value, and the family gets it as loaded, lists as lists and dicts as
dicts), samples(cfg) (the job's number of training samples),
make_shards(key, workers, cfg, mesh) (the workers' data from the seed,
(K, samples // K, ...)), init_params(key, cfg) (the weights, in the
program's parameter tree), noise(key, n, cfg) (the generator's input),
and generator(gen, z, cfg, variant) and discriminator(disc, x, cfg,
variant), at `Precision.HIGHEST`, where variant "fp8" is the family's
precision one step below the configuration's.
`families/<family>/program.py` is the family's only file that imports
the program: spec(cfg) returns the program's `GanModelSpec`.
`flops/<family>.py` counts a round's FLOPs and the Algorithm-2 bytes.

The reference runs a round on one chip, mesh cells too, in float32. It
holds the arguments, the generator and the discriminator (P_g + P_d
parameters), and temporaries of about 7 P_d whatever K is (Algorithm 2
is a running sum over the workers), besides the stacked fakes
(n_d x m_k x a sample's shape) and the family's own activations. A
family keeps its activations down inside its own `generator` and
`discriminator`, by `jax.checkpoint` or by mapping over chunks of the
batch, which changes no math.
"""
