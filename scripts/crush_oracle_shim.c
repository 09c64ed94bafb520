/* Dev-time oracle shim: exposes the reference CRUSH C core
 * (/root/reference/src/crush — builder.c/mapper.c/crush.c/hash.c) through a
 * flat C ABI so scripts/gen_crush_fixtures.py can drive it via ctypes and
 * pin fixture vectors for the Python/JAX engines.
 *
 * Build (see scripts/build_crush_oracle.sh):
 *   gcc -O2 -shared -fPIC -I. -I$REF/src -I$REF/src/crush \
 *       crush_oracle_shim.c $REF/src/crush/{builder,mapper,crush,hash}.c \
 *       -o /tmp/crush_oracle/libcrush_oracle.so -lm
 *
 * This file contains no reference code — only calls into its public API.
 */
#include <stdlib.h>
#include <string.h>

#include "crush/crush.h"
#include "crush/builder.h"
#include "crush/mapper.h"
#include "crush/hash.h"

struct crush_map *oracle_create(void)
{
	return crush_create();
}

void oracle_set_tunables(struct crush_map *m, int local_tries,
			 int local_fallback_tries, int total_tries,
			 int descend_once, int vary_r, int stable)
{
	m->choose_local_tries = local_tries;
	m->choose_local_fallback_tries = local_fallback_tries;
	m->choose_total_tries = total_tries;
	m->chooseleaf_descend_once = descend_once;
	m->chooseleaf_vary_r = vary_r;
	m->chooseleaf_stable = stable;
}

int oracle_add_bucket(struct crush_map *m, int alg, int type, int n,
		      int *items, int *weights, int want_id)
{
	struct crush_bucket *b;
	int id = 0;

	b = crush_make_bucket(m, alg, CRUSH_HASH_RJENKINS1, type, n,
			      items, weights);
	if (!b)
		return 0x7fffffff;
	if (crush_add_bucket(m, want_id, b, &id) < 0)
		return 0x7fffffff;
	return id;
}

int oracle_add_rule(struct crush_map *m, int n, int *ops, int *arg1,
		    int *arg2)
{
	struct crush_rule *r = crush_make_rule(n, 0, 1, 1, 10);
	int i;

	if (!r)
		return -1;
	for (i = 0; i < n; i++)
		crush_rule_set_step(r, i, ops[i], arg1[i], arg2[i]);
	return crush_add_rule(m, r, -1);
}

void oracle_finalize(struct crush_map *m)
{
	crush_finalize(m);
}

int oracle_do_rule(struct crush_map *m, int ruleno, int x, int *result,
		   int result_max, unsigned *weights, int weight_max)
{
	char *work = malloc(crush_work_size(m, result_max));
	int n;

	crush_init_workspace(m, work);
	n = crush_do_rule(m, ruleno, x, result, result_max,
			  weights, weight_max, work, NULL);
	free(work);
	return n;
}

unsigned oracle_hash32_2(unsigned a, unsigned b)
{
	return crush_hash32_2(CRUSH_HASH_RJENKINS1, a, b);
}

unsigned oracle_hash32_3(unsigned a, unsigned b, unsigned c)
{
	return crush_hash32_3(CRUSH_HASH_RJENKINS1, a, b, c);
}
