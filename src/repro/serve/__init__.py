from repro.serve.permanova import (  # noqa: F401
    PermanovaServer,
    RetryPolicy,
    ServeResult,
    ServerOverloaded,
    StudyRequest,
    mc_pvalue_ci,
    serve_stats_from_events,
)
