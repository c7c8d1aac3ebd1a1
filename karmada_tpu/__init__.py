"""karmada_tpu — a TPU-native multi-cluster orchestration framework.

A ground-up rebuild of the capabilities of Karmada (the CNCF multi-cloud
Kubernetes orchestrator, studied at /root/reference) with a TPU-first
architecture: the scheduler's Filter/Score/Select/AssignReplicas hot path is a
batched JAX kernel over a ``(bindings x clusters x resource-dims)`` tensor
program, while the control plane around it (store, controllers, estimators,
interpreter) is an idiomatic Python reconciliation runtime.

Layer map (mirrors SURVEY.md section 1):

- :mod:`karmada_tpu.api`        — typed data model (ref: pkg/apis/*)
- :mod:`karmada_tpu.utils`      — store/watch bus, workers, quantities
                                  (ref: pkg/util)
- :mod:`karmada_tpu.ops`        — pure jittable tensor kernels: bitset masks,
                                  the vectorized Dispenser, division strategies
- :mod:`karmada_tpu.scheduler`  — snapshot packing + plugin framework + the
                                  batched scheduling core (ref: pkg/scheduler)
- :mod:`karmada_tpu.estimator`  — general + accurate capacity estimators
                                  (ref: pkg/estimator)
- :mod:`karmada_tpu.models`     — cluster resource modeling / grade buckets
                                  (ref: pkg/modeling)
- :mod:`karmada_tpu.controllers`— propagation/status/failover reconcilers
                                  (ref: pkg/controllers, pkg/detector)
- :mod:`karmada_tpu.interpreter`— resource interpreter facade
                                  (ref: pkg/resourceinterpreter)
- :mod:`karmada_tpu.parallel`   — device-mesh sharding of the solver
- :mod:`karmada_tpu.refimpl`    — pure-Python oracle of the reference's
                                  division semantics (test baseline)
"""

__version__ = "0.1.0"
