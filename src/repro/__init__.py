"""repro: 'Computing Treewidth on the GPU' as a multi-pod JAX/TPU framework.

Public entry points:
  repro.core.ladder.solve / solve_many / solve_distributed
  repro.models.Model + repro.configs.get_config
  repro.launch.{dryrun,train,serve,solve,supervisor}
"""
