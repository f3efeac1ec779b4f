from .optimizer import (Optimizer, adamw, clip_by_global_norm, constant_schedule,
                        cosine_schedule, global_norm, linear_warmup_cosine,
                        make_optimizer, optimizer_hypers, sgd)
from .train_step import (StepCacheInfo, TrainState, make_eval_step,
                         make_hyper_train_step, make_train_state, make_train_step,
                         shared_train_step, step_cache_clear, step_cache_info)
from .serve_step import generate, make_decode_step, make_prefill_step, sample_tokens
