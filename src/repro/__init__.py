"""repro — cross-layer scientific-workflow / large-model systems reproduction.

Layer map (see README.md):
  core     workflow DAG, scheduler, location-aware store, compiler hints
  dist     runtime sharding rules + hint resolution + compressed collectives
  models   the 10 architecture families (pure-functional jax)
  train    loop, optimizer, checkpoint, elastic restart
  serve    decode engine
  launch   meshes, input specs, dry-run lowering of every (arch×shape) cell
"""
