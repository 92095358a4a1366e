"""Operations and bytes from shapes. One module a count, each with
``count(config, traffic) -> {"flops", "bytes"}`` for ONE unit of work (one
fit job, one row scored), every float32 multiply-add counted once as two
operations whatever the number of bf16 passes the chip makes of it."""
