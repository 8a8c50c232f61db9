"""Operations and bytes that the algorithm needs, counted from shapes:
one module per kernel or step, named by the configuration files or by the
metric readers that use them."""
