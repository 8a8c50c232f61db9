"""Plain references, independent of the program: the federated job
(``federated``) and one module per model, named by each configuration's
``reference`` key. They compute in float32 with every contraction at
``Precision.HIGHEST`` (on a TPU a float32 product otherwise takes one
bfloat16 pass), or wholly in a narrower type for the control."""
